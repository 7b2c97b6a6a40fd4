"""Traffic driver ``train_steps_mimo``: the closed loop of ``train_steps``
(steps back to back, one queued ahead, the window ends on the last loss)
over the mimo_v2 block of ``accl_tpu.models`` (MiMo-V2.5) as ONE chip of a
16-way expert-parallel group computes it: five sliding-window layers (a
window of 128 keys, narrower than a flash tile; 8 KV heads; a learned sink a
query head in the softmax; rope base 1e4) to one full-attention layer (4 KV
heads, no sink, rope base 1e7), every head 192 columns of q and k of which
the first 64 rotate beside v heads of 128 scaled by 0.707, a leading dense
layer, a sigmoid router with a selection bias, top 8 of 256 with 16 held;
through ``make_sharded_train_step`` on a world of one chip.

Set-up builds the program's config FIRST, so a tree whose ``accl_tpu.models``
lacks the block fails at once (the parent of PR 54 has no ``HeadGeometry``).
The weights are the seed's, the sinks uniform in [2, 6] from the seed (the
configuration file's ``departures`` says why); the selection bias is then
brought to balance by twelve rounds of its own rule (``train_steps_trinity.
balanced``).  Then the check, on the first batch, against the plain float32
reference in ``perfbench/reference/mimo_v2.py`` (given the same held range;
a layer at a time, so that one layer's float32 weights are alive at once):

* (a) the router's counters through ``make_sharded_router_probe``: tokens an
  expert a layer over all 256 and the entries held here against the
  reference's, both within the count of near-tie tokens; nothing dropped;
* (b) logits of the batch's one sequence through ``make_sharded_forward``,
  its FIRST ``check_first`` positions (the window not yet full on half of
  them, the sink's share largest) and its LAST ``check_positions``: a row's
  relative error at its median, and all the rows' relative RMS and largest
  error;
* (c) the loss the FIRST train step returns (through
  ``make_sharded_train_step`` itself) against the reference's of the batch;
* (d) the UPDATE, against the reference's gradients (taken a layer at a
  time, last layer first), the sinks' among them: what the first step of the
  compiled step the window times did to every leaf, as far as bf16 SGD at
  the cell's rate shows a gradient at all, and what the same step compiled
  at UPDATE_PROBE_RATE did, where every leaf shows it (``train_steps_
  nemotron3``'s ``_moved``, inherited; its limits' comment says what each
  reading can and cannot see);
* (e) the compiled step holds the flash kernels under BOTH attention scopes,
  one backward call a layer of each kind (0 means ``auto`` fell through to
  an XLA form, which is not the path the cell times).

The reference's pass also reads the sink's share of a row's probability,
sliding layer by sliding layer (``reference.sink_facts``): the run's
``sink_share`` fact.

``setup`` is ``prepare`` (the program's side), ``judge`` (the reference's
side and the comparison) and ``warm_up``; ``perfbench/controls_mimo.py``
plants faults through ``judge``'s arguments, and each has to end not correct.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from perfbench import flops_mimo, scope_ops
from perfbench.drivers import train_steps_nemotron3, train_steps_trinity
from perfbench.drivers.train_steps_nemotron3 import (
    BALANCE_RATES,
    UPDATE_MIN_IN_PLAY,
    UPDATE_PROBE_RATE,
    fp8,
)
from perfbench.drivers.train_steps_trinity import balanced, held_entries
from perfbench.layer_metrics._afmoe import CORE, WINDOW
from perfbench.reference import mimo_v2 as reference

#: Limits of the check: the program (bf16 weights and activations, f32
#: accumulation, f32 softmax statistics and router sigmoid) against the
#: float32 reference at "highest" matmul precision.  Measured on the v5e at
#: the published widths and seven layers (my chip runs, PR 54: NINE seeds
#: BEFORE these limits were set, the cell at 3000000019, 4000000007,
#: 1618033989, 2971215091, 1134903217, 3524578003, 1234567891, 2147483659
#: and the controls' set-up at 2178309011, judged at Nemotron-3's limits;
#: the runs after them are in ``PERF.md`` section 4).
#:
#: ROUTING NEAR-TIES, as ``train_steps_nemotron3``: bf16 rounding of the
#: hidden state can swap a token's 8th and 9th expert where the float32
#: reference does not.  A token is NEAR A TIE in a layer where that gap of
#: its selection scores (``sigmoid + bias``) is within NEAR_TIE_SPACINGS bf16
#: spacings (2^-8 of the layer's score RMS): 684-1,234 of a layer's 8,192
#: tokens (2,438-3,971 within one spacing).  (a) Half the L1 distance between
#: the program's tokens-an-expert histogram over all 256 and the reference's
#: must stay under that count: read 224-303 entries a layer, largest ratio
#: 0.36; so must the difference in the entries HELD here (read 1-33; held
#: 3,496-4,560 of the buffer's 8,192 rows, the balanced share 4,096); none
#: dropped.
#:
#: (b) LOGITS, ALL 768 CHECKED ROWS (logits of RMS 1.34-1.38): a row's
#: relative error (L2 over the vocabulary) at its MEDIAN 0.738-0.879%; the
#: rows' relative RMS 1.060-1.179%; the largest error 0.199-0.266.  The EARLY
#: rows read half again the late ones (medians 1.14-1.24% against 0.72-0.86%):
#: fewer keys to average bf16's rounding over, and the sink's share largest.
#: (c) The first step's loss against the reference's: 1.7e-6 to 2.1e-5 apart
#: (loss 10.65-10.69 on uniform ids: ln 19,072 = 9.86 and the seeded head's spread).
#:
#: WHICH SCALE KEEPS WHAT ALIVE (``blocks_added``, the reference's RMS of what
#: a layer adds over the RMS of the stream it adds to): 143-148 (layer 0 on
#: the 0.02 embedding), then 0.16-0.18, 0.18-0.20, 0.20-0.23, 0.22-0.26,
#: 0.26-0.31 (the five sliding expert layers) and 0.25-0.33 (the full one): no
#: layer is dead.  At seeded weights the scores are NOT near 0 (q and k of std
#: 1.28 over 192 columns: a score's std is 1.6), so position matters: the
#: three wrong rotations move the logits by 70-89%.  THE SINK (``sink_share``,
#: drawn in [2, 6]): its share of a row's probability is 0.0000-1.0000 over
#: rows and heads, median 2.7-15.6%, mean 27-37% on the rows whose window is
#: still filling and 5.9-18.6% where it is full.
#:
#: CONTROLS (``perfbench/controls_mimo.py``, seed 2178309011, through
#: ``judge`` itself, at Nemotron-3's limits before these were set), as median
#: row, relative RMS, largest error, loss apart, the timed step's worst leaf,
#: the probe step's: the sound reference 0.830%, 1.144%, 0.211, 2.1e-5, 0.0012,
#: 0.223.  The NEAREST controls are the window one key short or long (one key
#: of 128 in five layers): 1.605%, 1.984%, 0.355, 2.9e-5, 0.015, 0.320 and
#: 1.607%, 1.971%, 0.299, 4.6e-5, 0.015, 0.347.  A sink on the full layers too
#: (4.0 a head against a row of up to 8,192 keys: only the first rows see it):
#: 0.838% (passes), 16.5%, 5.94, 1.0e-4, 0.061, 0.498.  No sink: 10.0%, 18.2%,
#: 3.90, 1.6e-4, 0.162, 0.774 (1,479-6,206 entries a layer moved against 730-
#: 1,059 allowed).  The sink with a value: 16.0%, 26.0%, 5.22, 3.0e-4, 0.287,
#: 1.37 (a sink's own gradient).  Every column rotating, the last 64 rotating,
#: the two thetas swapped: 81.1 / 76.7 / 69.8%, 88.8 / 81.2 / 74.1%, 7.78 /
#: 7.21 / 5.98, 5.4e-4 to 6.3e-4, 0.76-0.79, 1.45-1.51.  No value scale: 26.6%,
#: 26.6%, 2.03, 3.9e-6 (passes), 0.430, 1.79.  Four KV heads in both kinds:
#: 28.8%, 30.3%, 2.48, -, 0.964, 2.96.  The nearest precision below bf16, the
#: reference from weights rounded to e5m2: 93.4%, 80.4%, 6.11, 3.2e-3, 0.875,
#: 1.80 (11,416-28,359 entries a layer moved).  A state left unchanged: (b)
#: and (c) the sound reference's, 0.834, 1.0.
#: THE LIMITS.  The median row's 1.2% and the relative RMS's 1.52% are the
#: geometric means of the nine seeds' largest reading and the NEAREST control's
#: smaller reading (0.879 and 1.605: 1.37 and 1.34 times of room; 1.179 and
#: 1.971: 1.29 and 1.30): the readings are steady over seeds (0.74-0.88,
#: 1.06-1.18) because all 768 rows are judged and a swapped expert moves one of
#: eight entries.  The largest error cannot tell a window of 127 (0.30-0.36 is
#: inside a maximum's tail over 1.5e7 samples): its 0.7 lies between 0.266 and
#: the nearest control it CAN tell, no value scale's 2.03 (2.6 and 2.9 times).
#: (c)'s 2.5e-4 is the geometric mean of 2.1e-5 and e5m2's 3.2e-3 (12 and 13
#: times): uniform ids at seeded weights hardly see a mixer's detail, and five
#: controls pass it.
#:
#: (d) THE UPDATE, as ``train_steps_nemotron3`` (its comment says what each of
#: the two readings can and cannot see).  THE TIMED STEP: 16-18 of the tree's
#: 63 leaves have UPDATE_MIN_IN_PLAY elements in play (the table 96,000-
#: 115,000, the head 21,000-23,000, layer 0's ``wk`` / ``wv`` 13,000-26,000,
#: the later layers' ``wv`` 220-1,500 and ``wo`` 33-190; no routed expert, no
#: router, no ``wq``, and 1-2 sinks of 320 by a float32 spacing); the worst
#: leaf is layer 0's ``wk``, 0.0003-0.0012 (7-27 elements of 22,500-26,300),
#: and every small leaf read 0 off; a state left unchanged 0.834, e5m2 0.875,
#: the windows of 127 / 129 0.015, no sink 0.162.  Its limit stays Nemotron-3's
#: 0.3, far above the reading ON PURPOSE: a late ``wo`` has 33-50 elements in
#: play, ONE of them off reads 0.03 and two 0.06, and fresh seeds draw such
#: leaves; 0.3 is 2.8 times under an unchanged state.  THE PROBE STEP, every
#: leaf's ``|probed - before + rate g| / |rate g|``: 0.01-0.02 on the sinks,
#: 0.02-0.07 on the mixers', layer 0's and the head's leaves, 0.19-0.27 on the
#: routers and the norms before them (their rows are the routing's), the worst
#: always an expert layer's router, 0.223-0.268; a state left unchanged 1.0 on
#: every leaf, a sink on the full layers 0.498, the windows of 127 / 129
#: 0.32-0.35, every other wrong reference 0.77-2.96.  Its limit 0.45 lies
#: between 0.268 and the nearest control it can tell, 0.498 (1.68 and 1.11
#: times; an unchanged state is 2.2 times past it).  NOT judged: the selection
#: bias's move by its rule (outside the gradient); the timed step's update of
#: the leaves it does not change.
ROW_MEDIAN_LIMIT = 0.012
REL_RMS_LIMIT = 0.0152
MAX_ABS_LIMIT = 0.7
LOSS_REL_LIMIT = 2.5e-4
NEAR_TIE_SPACINGS = 0.25
UPDATE_TIMED_LIMIT = 0.3
UPDATE_PROBE_LIMIT = 0.45
#: what a run prints beside what it is judged by, for the next look: the
#: count of near-tie tokens at other margins, a row's error at quantiles
_NEAR_TIE_LOOK = (0.125, 0.25, 0.5, 1.0, 2.0)
_ROW_LOOK = (0.1, 0.5, 0.9, 0.99, 1.0)
#: the seeded sinks' range (the configuration file's ``departures``)
SINK_RANGE = (2.0, 6.0)
#: the flash kernels, by the name of their custom calls
FLASH_KERNELS = ("flash_fwd", "flash_bwd")


def program_config(config: dict):
    """The published keys as ``accl_tpu.models.TransformerConfig``: what
    differs by layer kind on ``LayerKind``, the heads' geometry as ONE
    ``HeadGeometry``."""
    import jax.numpy as jnp

    from accl_tpu.models import HeadGeometry, LayerKind, TransformerConfig

    if config["model_type"] != "mimo_v2":
        raise ValueError("the block is mimo_v2's")
    heads, hd = config["num_attention_heads"], config["head_dim"]
    if (
        config["attention_bias"] or config["add_full_attention_sink_bias"]
        or not config["add_swa_attention_sink_bias"]
        or config["swa_num_attention_heads"] != heads
        or config["swa_head_dim"] != hd
        or config["swa_v_head_dim"] != config["v_head_dim"]
        or config["sliding_window_size"] != config["sliding_window"]
        or config["scoring_func"] != "sigmoid"
        or config["topk_method"] != "noaux_tc"
        or (config["n_group"], config["topk_group"]) != (1, 1)
        or config["n_shared_experts"] or not config["norm_topk_prob"]
        or config["routed_scaling_factor"] is not None
        or config["hidden_act"] != "silu" or config["tie_word_embeddings"]
        or config["rope_scaling"]["rope_type"] != "default"
    ):
        raise ValueError(
            "the variant is 64 heads of one geometry in both kinds, a sink "
            "on the sliding layers alone, no bias, plain rope, the sigmoid "
            "router under noaux_tc without a group limit, a shared expert "
            "or a scale, its top-k renormalised, gated-SiLU, the head untied"
        )
    kept = config["layers_kept"]
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError("layers_kept does not list num_hidden_layers layers")
    geometry = HeadGeometry(
        rope_dim=int(hd * config["partial_rotary_factor"]),
        v_dim=config["v_head_dim"],
        v_scale=float(config["attention_value_scale"]),
    )
    program = config["program"]

    def kind(swa: bool, moe: bool):
        return LayerKind(
            window=config["sliding_window"] if swa else None,
            kv_heads=flops_mimo.kv_heads(config, swa),
            rope_base=float(config["swa_rope_theta" if swa else "rope_theta"]),
            sink=swa, heads=geometry,
            ffn="moe" if moe else "dense",
            d_ff=config["moe_intermediate_size" if moe else "intermediate_size"],
        )

    held, of = config["n_routed_experts"], config["num_router_experts"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=hd,
        n_layers=config["num_hidden_layers"],
        layers=tuple(kind(*k) for k in flops_mimo.layer_kinds(config)),
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]
        ],
        pos_embedding="rope",
        rope_base=float(config["rope_theta"]),
        norm="rmsnorm",
        norm_eps=float(config["layernorm_epsilon"]),
        ffn="swiglu",
        tie_head=config["tie_word_embeddings"],
        n_experts=held,
        moe_top_k=config["num_experts_per_tok"],
        moe_capacity_factor=None,
        moe_norm_topk_prob=config["norm_topk_prob"],
        moe_aux_weight=0.0,
        moe_router_z_weight=0.0,
        moe_router=config["scoring_func"],
        moe_bias_rate=float(config["bias_update_speed"]),
        moe_router_experts=None if of == held else of,
        moe_first_expert=config["first_expert"],
        moe_held_row_factor=float(program["held_row_factor"]),
        attention=program["attention"],
        remat=program["remat"],
    )


def reference_model(config: dict) -> dict:
    """The keyword arguments ``reference.layer`` takes, from the keys."""
    hd = config["head_dim"]
    return dict(
        n_head=config["num_attention_heads"], head_dim=hd,
        rotary=int(hd * config["partial_rotary_factor"]),
        thetas=(float(config["rope_theta"]), float(config["swa_rope_theta"])),
        window=config["sliding_window"],
        v_scale=float(config["attention_value_scale"]),
        top_k=config["num_experts_per_tok"],
        first_expert=config["first_expert"],
    )


def reference_block(lp: dict) -> dict:
    """One layer of the program's parameter tree under mimo_v2's names (the
    held experts' matrices stacked on a leading axis)."""
    out = {
        "input_layernorm": lp["ln1"], "post_attention_layernorm": lp["ln2"],
        "q_proj": lp["wq"], "k_proj": lp["wk"], "v_proj": lp["wv"],
        "o_proj": lp["wo"],
    }
    if "sink" in lp:
        out["attention_sink_bias"] = lp["sink"]
    if "moe" not in lp:
        return dict(out, **{
            "mlp.gate_proj": lp["w1"], "mlp.up_proj": lp["w3"],
            "mlp.down_proj": lp["w2"],
        })
    moe = lp["moe"]
    return dict(out, **{
        "router": moe["gate"], "e_score_correction_bias": moe["bias"],
        "experts.gate_proj": moe["w1"], "experts.up_proj": moe["w3"],
        "experts.down_proj": moe["w2"],
    })


def reference_top(params: dict) -> dict:
    """The tree's leaves outside the layers under the reference's names."""
    return {
        "embed_tokens": params["embed"], "norm": params["ln_f"],
        "lm_head": params["head"],
    }


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the reference's names."""
    return dict(
        reference_top(params),
        layers=[reference_block(lp) for lp in params["layers"]],
    )


def seeded_params(key, cfg):
    """``init_params`` with every sink drawn uniform in ``SINK_RANGE``."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.models import init_params

    params = init_params(key, cfg)
    for i, lp in enumerate(params["layers"]):
        if "sink" in lp:
            lp["sink"] = jax.random.uniform(
                jax.random.fold_in(key, 1000 + i), lp["sink"].shape,
                jnp.float32, *SINK_RANGE,
            )
    return params


def tile_facts(T: int, window: int, tile: int = 512) -> dict:
    """What the flash kernels visit a head against what a query sees, a
    sliding layer and a full one: tile pairs visited (by the kernels' own
    ranges), the (q, k) pairs those tiles multiply, the pairs inside the
    mask."""
    from accl_tpu.ops.pallas.attention import flash_tile_pairs

    tile = min(tile, T)
    out = {"tile": tile}
    for name, w in (("swa", window), ("full", None)):
        visited = flash_tile_pairs(T, tile, w)
        out[name] = {
            "tile_pairs": visited, "multiplied_pairs": visited * tile * tile,
            "seen_pairs": flops_mimo.attended_pairs(T, w),
        }
    return out


class Driver(train_steps_nemotron3.Driver):
    """``setup``, ``warm_up`` and ``_moved`` are ``train_steps_nemotron3``'s,
    ``_segment`` and ``_note_loss`` ``train_steps``'; ``measure`` adds the
    mixers' facts to ``train_steps_trinity``'s."""

    def prepare(self) -> None:
        """Everything up to the first train step: what the program gives on
        the first batch (logits, router counters, the loss and the updated
        weights of the compiled step the window times), and the weights as
        they were before it; both sets of weights on the host."""
        cfg = program_config(self.config)   # first: see the module docstring

        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from accl_tpu.models import (
            make_sharded_forward,
            make_sharded_router_probe,
            make_sharded_train_step,
        )
        from accl_tpu.models.transformer import (
            normalize_spec,
            param_specs,
            resolve_attention,
        )

        self._mark("imports")
        tr = self.traffic
        B, T = int(tr["batch"]), int(tr["seq"])
        if T > cfg.max_seq:
            raise ValueError(
                f"seq {T} past max_position_embeddings {cfg.max_seq}"
            )
        self.B, self.T, self.cfg = B, T, cfg
        mesh = Mesh(np.array([self.device]).reshape(1, 1), ("dp", "tp"))
        kinds = flops_mimo.layer_kinds(self.config)
        swa = sum(s for s, _ in kinds)
        self.mixers = {
            "swa_layers": swa, "full_layers": len(kinds) - swa,
            "expert_layers": sum(m for _, m in kinds),
            "tiles": tile_facts(T, self.config["sliding_window"]),
        }

        # ``auto`` is decided on the part of a head WITHOUT position: the
        # rotating part rides beside it on lanes of its own
        geometry = cfg.layers[0].heads
        q = jax.ShapeDtypeStruct(
            (B, cfg.n_heads, T, cfg.head_size() - geometry.rope_dim),
            jnp.dtype(cfg.dtype),
        )
        self.attention = resolve_attention(cfg.attention, q)
        if not self.rehearse and self.attention != "flash":
            self.problems.append(
                f"attention={cfg.attention!r} resolved to "
                f"{self.attention!r}, not 'flash'"
            )

        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, normalize_spec(s)),
            param_specs(cfg),
            is_leaf=lambda x: isinstance(x, P),
        )
        replicated = NamedSharding(mesh, P())
        key = jax.device_put(jax.random.PRNGKey(self.seed), replicated)
        params = jax.jit(
            lambda k: seeded_params(k, cfg), out_shardings=shardings
        )(key)

        n = int(tr["token_batches"])

        def make_tokens(k):
            # ids from the held slice of the vocabulary: cfg.vocab rows
            tok = jax.random.randint(
                jax.random.fold_in(k, 1), (n, B, T), 0, cfg.vocab, jnp.int32
            )
            return tok, jnp.roll(tok, -1, axis=-1)

        tok, tgt = jax.jit(
            make_tokens, out_shardings=(replicated, replicated)
        )(key)
        self.tokens, self.targets = list(tok), list(tgt)
        jax.block_until_ready((params, tok))
        self._mark("weights_and_tokens")

        fwd, _ = make_sharded_forward(cfg, mesh)
        probe = make_sharded_router_probe(cfg, mesh)
        bias_sharding = next(
            s["moe"]["bias"] for s in shardings["layers"] if "moe" in s
        )
        # ``train_steps_trinity``'s rounds (its rule is this model's too)
        params = balanced(
            probe, params, self.tokens,
            lambda bias: jax.device_put(bias, bias_sharding), BALANCE_RATES,
        )
        self._mark("bias_balanced")

        rows, _ = self._checked_rows()
        self.got = {
            "logits": np.asarray(jax.jit(
                lambda z: z[0][rows].astype(jnp.float32)
            )(fwd(params, self.tokens[0]))),
            "counters": jax.device_get(probe(params, self.tokens[0])),
        }
        # the step gives its argument's memory to its result
        self.before = jax.device_get(params)
        self._mark("program_forward")

        # the same step at UPDATE_PROBE_RATE, from the same weights and batch:
        # what its update shows of the gradient
        probe_step, _ = make_sharded_train_step(cfg, mesh, lr=UPDATE_PROBE_RATE)
        params, loss = probe_step.lower(
            params, self.tokens[0], self.targets[0]
        ).compile()(params, self.tokens[0], self.targets[0])
        self.got["probe_loss"] = float(loss)
        self.probed = jax.device_get(params)
        # two copies of 6.86 GB of weights would not leave the step its room
        del probe_step, params
        params = jax.device_put(self.before, shardings)
        self._mark("probe_step")

        # compiled ONCE, ahead of time: nothing can compile in the window
        step, _ = make_sharded_train_step(cfg, mesh, lr=float(tr["lr"]))
        self.step = step.lower(params, self.tokens[0], self.targets[0]).compile()
        # the flash kernels under each attention scope in the compiled step,
        # by name (a rehearsal's sizes run the naive form)
        scoped = scope_ops.scopes_of(self.step.as_text())
        self.flash_calls = None if self.rehearse else {
            scope: {
                kernel: sum(kernel in name for name in scoped.get(scope, ()))
                for kernel in FLASH_KERNELS
            }
            for scope in (WINDOW, CORE)
        }
        mem = self.step.memory_analysis()
        live = (self.device.memory_stats() or {}).get("bytes_in_use", 0)
        # as train_steps: the arrays alive at a step's start, its outputs
        # that alias no argument, and its scratch
        self.step_peak_bytes = int(
            live + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
        ) if mem is not None else 0
        self.step_memory = None if mem is None else {
            "live_bytes": int(live),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        }
        self._mark("step_compiled")
        params, loss = self.step(params, self.tokens[0], self.targets[0])
        self.got["loss"] = float(loss)
        # off the device while the reference works there
        self.after = jax.device_get(params)
        self._shardings = shardings
        self._mark("first_step")

    # -- the check -----------------------------------------------------------

    def _reference(self, rows, fp8_weights: bool = False,
                   unchanged_state: bool = False, **how):
        """The reference on the first batch from the weights as they were
        before the first step, a layer at a time, forwards and then
        backwards: logits of the sequence's positions ``rows``, the loss,
        each expert layer's routing facts, each sliding layer's sink facts,
        what each layer adds to the stream (the RMS of ``f`` over the RMS of
        ``h``), and the gradients, which ``_moved`` holds against the two
        steps' updates leaf by leaf, in the program's names.
        ``fp8_weights``: the reference from weights rounded to e5m2; ``how``:
        keyword arguments that break a layer (the controls of
        ``perfbench/controls_mimo.py``), as is ``unchanged_state``: the
        weights before the step in the place of those after it."""
        import jax
        import jax.numpy as jnp

        model = dict(reference_model(self.config), q_block=min(512, self.T))
        # two controls are said against the file's own numbers
        model["window"] += how.pop("window_off", 0)
        if how.pop("swap_thetas", False):
            model["thetas"] = model["thetas"][::-1]
        model.update(how)
        top_k, window = self.cfg.moe_top_k, model["window"]
        tokens, targets = self.tokens[0], self.targets[0]
        f32 = lambda tree: jax.tree.map(lambda p: p.astype(jnp.float32), tree)
        rounded = fp8 if fp8_weights else (lambda tree: tree)
        block = lambda h, lp, swa: reference.layer(
            h, reference_block(lp), swa=swa, **model
        )

        @functools.partial(jax.jit, static_argnames="swa")
        def one_layer(h, lp, swa):
            with jax.default_matmul_precision("highest"):
                new, picked, p_sink = block(h, rounded(lp), swa)
            rms = lambda x: jnp.sqrt(jnp.mean(jnp.square(x)))
            return (
                new, rms(new - h) / rms(h),
                None if picked is None
                else reference.routing_facts(picked, top_k),
                reference.sink_facts(p_sink, window) if swa else None,
            )

        @functools.partial(jax.jit, static_argnames="swa")
        def one_layer_back(h, lp, d_out, swa):
            with jax.default_matmul_precision("highest"):
                _, back = jax.vjp(
                    lambda h, lp: block(h, lp, swa)[0], h, f32(rounded(lp))
                )
                return back(d_out)

        def ends(top, h):
            weights = reference_top(top)
            return (
                reference.head(weights, h[0][rows]),
                reference.nll_from_hidden(weights, h, targets),
            )

        @jax.jit
        def finish(top, h):
            with jax.default_matmul_precision("highest"):
                (want, loss), back = jax.vjp(ends, f32(rounded(top)), h)
                d_top, d_h = back((jnp.zeros_like(want), jnp.ones_like(loss)))
            return want, loss, d_top, d_h

        @jax.jit
        def embed_back(table, d_h):
            _, back = jax.vjp(
                lambda table: reference.embed(
                    {"embed_tokens": table["embed"]}, tokens
                ),
                f32(rounded(table)),
            )
            return back(d_h)[0]

        kinds = [swa for swa, _ in flops_mimo.layer_kinds(self.config)]
        top = jax.device_put(
            {k: v for k, v in self.before.items() if k != "layers"}
        )
        h = jax.jit(
            lambda top: reference.embed(reference_top(rounded(top)), tokens)
        )(top)
        facts, added, inputs, sinks = [], [], [], []
        for lp, swa in zip(self.before["layers"], kinds):
            inputs.append(h)
            h, layer_added, routing, sink = one_layer(
                h, jax.device_put(lp), swa=swa
            )
            added.append(float(layer_added))
            if routing is not None:
                facts.append(routing)
            if sink is not None:
                sinks.append({
                    k: np.asarray(v).tolist() for k, v in sink.items()
                })
        want, loss, d_top, d_h = finish(top, h)
        moved = {}

        def stepped(before, part):
            """``part`` of the weights after the timed step and after the
            probe step."""
            if unchanged_state:
                return before, before
            return jax.device_put((part(self.after), part(self.probed)))

        for i in reversed(range(len(inputs))):
            lp = jax.device_put(self.before["layers"][i])
            d_h, d_lp = one_layer_back(inputs.pop(), lp, d_h, swa=kinds[i])
            moved.update(self._moved(
                f"{i}.", lp, *stepped(lp, lambda tree: tree["layers"][i]), d_lp
            ))
            del d_lp                # a layer's float32 gradients: 1.6 GB
        d_top.update(embed_back({"embed": top["embed"]}, d_h))
        moved.update(self._moved(
            "", top, *stepped(top, lambda tree: {k: tree[k] for k in top}),
            d_top,
        ))
        self.blocks_added, self.sink_share = added, sinks
        return np.asarray(want), float(loss), facts, moved

    def _checked_rows(self):
        """The sequence's positions whose logits are compared: its first
        ``check_first`` and its last ``check_positions`` (no more than half
        of it each), and how many of them are the early ones."""
        early = min(int(self.traffic["check_first"]), self.T // 2)
        late = min(int(self.traffic["check_positions"]), self.T // 2)
        return np.concatenate(
            [np.arange(early), np.arange(self.T - late, self.T)]
        ), early

    def judge(self, **fault) -> None:
        """What ``prepare`` kept of the program against the reference;
        ``fault`` (``_reference``'s arguments) plants one, for the
        controls."""
        cfg = self.cfg
        rows, span = self._checked_rows()
        got, counters = self.got["logits"], self.got["counters"]
        first, held = cfg.moe_first_expert, cfg.n_experts
        want, want_loss, facts, moved = self._reference(rows, **fault)
        want_counts = np.stack([np.asarray(f[0]) for f in facts])
        gaps = np.stack([np.asarray(f[1]) for f in facts])          # (L, N)
        # a swapped expert moves one of a token's entries
        allowed = (gaps < NEAR_TIE_SPACINGS).sum(axis=1)
        near = np.stack([(gaps < m).sum(axis=1) for m in _NEAR_TIE_LOOK])

        err, ref = got - want, np.sum(want ** 2, axis=1)
        by_row = np.sqrt(np.sum(err ** 2, axis=1) / ref)
        rel_rms = np.sqrt(np.sum(err ** 2) / np.sum(ref))
        max_abs = np.max(np.abs(err))
        of = lambda x: [float(v) for v in np.quantile(x, _ROW_LOOK)]
        row_median = float(np.median(by_row))
        counts = np.asarray(counters["expert_tokens"])
        dropped = int(np.asarray(counters["dropped"]).sum())
        here = np.asarray(counters["held_entries"])
        want_here = held_entries(want_counts, first, held)
        # an entry that went to another expert than the reference's moves
        # one count down and one up: half the L1 distance of the two
        # histograms is a lower bound on such entries, a layer
        moved_entries = np.abs(counts - want_counts).sum(axis=1) // 2
        held_off = np.abs(here - want_here)
        entries = counts.sum(axis=1)
        # by leaf: the timed step's share of elements in play left where no
        # rounding of the reference's update puts them; the probe step's
        # update off the reference's
        # (but the selection bias: outside the gradient, moved by its rule)
        moved = {k: s for k, s in moved.items() if not k.endswith("moe.bias")}
        timed = {
            name: s[1] / s[0]
            for name, s in moved.items() if s[0] >= UPDATE_MIN_IN_PLAY
        }
        probed = {
            name: math.sqrt(s[2] / s[3]) for name, s in moved.items() if s[3]
        }
        worst = lambda of: max(of, key=of.get) if of else None
        self.attempted += 1
        self.check = {
            "positions": len(rows),
            "rel_rms": float(rel_rms), "max_abs": float(max_abs),
            "row_median": row_median,
            # a row's relative error at _ROW_LOOK's quantiles: all the
            # checked rows, the early ones, the late ones
            "row_look": of(by_row),
            "early_late": {"early": of(by_row[:span]), "late": of(by_row[span:])},
            "reference_rms": float(np.sqrt(np.mean(want ** 2))),
            "attention": self.attention,
            "flash_calls": self.flash_calls,
            "dropped": dropped,
            "moved_entries": moved_entries.tolist(),
            "held_entries": here.tolist(),
            "reference_held_entries": want_here.tolist(),
            "allowed_entries": allowed.tolist(),
            "near_ties": {
                str(m): near[i].tolist() for i, m in enumerate(_NEAR_TIE_LOOK)
            },
            # the reference's RMS of what each layer adds over the RMS of
            # the stream it adds to, in the layers' order
            "blocks_added": self.blocks_added,
            # the sink's share of a row's probability, a sliding layer each
            "sink_share": self.sink_share,
            "update_timed_worst": timed.get(worst(timed)),
            "update_timed_worst_leaf": worst(timed),
            "update_timed_leaves": len(timed),
            "update_probe_worst": probed.get(worst(probed)),
            "update_probe_worst_leaf": worst(probed),
            "update_probe_sinks": {
                k: v for k, v in probed.items() if k.endswith("sink")
            },
            "probe_loss": self.got["probe_loss"],
            # by leaf: elements in play, the timed step's reading, the
            # probe step's
            "update": {
                name: [int(s[0]), timed.get(name), probed.get(name)]
                for name, s in moved.items()
            },
        }
        held_counts = counts[:, first:first + held]
        self.router = {
            "expert_tokens": counts.tolist(),
            # over the HELD experts: the load this chip's bank sees
            "load_imbalance": float(
                (held_counts.max(axis=1)
                 / np.maximum(held_counts.mean(axis=1), 1e-9)).max()
            ),
            "held_entries": here.tolist(),
            "entries": entries.tolist(),
            "held_entry_share": float(100.0 * here.sum() / entries.sum()),
        }
        bad = []
        if not (
            row_median <= ROW_MEDIAN_LIMIT and rel_rms <= REL_RMS_LIMIT
            and max_abs <= MAX_ABS_LIMIT
        ):
            bad.append(
                f"logits differ from the reference: the median row "
                f"{row_median:.4g} (limit {ROW_MEDIAN_LIMIT}), rel rms "
                f"{rel_rms:.4g} (limit {REL_RMS_LIMIT}), max abs "
                f"{max_abs:.4g} (limit {MAX_ABS_LIMIT}) over {len(rows)} rows"
            )
        if dropped:
            bad.append(
                f"{dropped} held routing entries past the row buffer "
                f"(held {here.tolist()})"
            )
        if (moved_entries > allowed).any():
            bad.append(
                f"tokens an expert: {moved_entries.tolist()} entries a layer "
                f"moved against the reference's routing, more than its "
                f"near-ties allow ({allowed.tolist()})"
            )
        if (held_off > allowed).any():
            bad.append(
                f"entries held here {here.tolist()} against the reference's "
                f"{want_here.tolist()}: further apart than its near-ties "
                f"allow ({allowed.tolist()})"
            )
        if not (
            len(timed) >= cfg.n_layers
            and timed[worst(timed)] <= UPDATE_TIMED_LIMIT
            and probed[worst(probed)] <= UPDATE_PROBE_LIMIT
        ):
            bad.append(
                f"the update differs from the reference's gradient: the "
                f"timed step's in {len(timed)} leaves, the worst "
                f"{worst(timed)} at {timed.get(worst(timed))} of its elements "
                f"in play (limit {UPDATE_TIMED_LIMIT}); the probe step's "
                f"worst {worst(probed)}, off by {probed[worst(probed)]:.4g} "
                f"of it (limit {UPDATE_PROBE_LIMIT})"
            )
        if self.flash_calls is not None:
            want_calls = {
                WINDOW: self.mixers["swa_layers"],
                CORE: self.mixers["full_layers"],
            }
            if any(
                self.flash_calls[scope]["flash_bwd"] != n
                or self.flash_calls[scope]["flash_fwd"] < n
                for scope, n in want_calls.items()
            ):
                bad.append(
                    f"the compiled step's flash kernels by scope "
                    f"{self.flash_calls}: not one backward call a layer "
                    f"({want_calls}), so auto fell through to an XLA form in "
                    "some layer, which is not the path this cell times"
                )
        if bad:
            self.failed += 1
            self.problems.extend(bad)
        self._check_loss(self.got["loss"], want_loss)

    def _check_loss(self, got: float, want: float) -> None:
        self.attempted += 1
        rel = abs(got - want) / abs(want)
        self.check.update(loss=got, reference_loss=want, loss_rel=rel)
        if not (math.isfinite(got) and rel <= LOSS_REL_LIMIT):
            self.failed += 1
            self.problems.append(
                f"first step's loss {got:.6g} against the reference's "
                f"{want:.6g}: {rel:.3g} apart (limit {LOSS_REL_LIMIT})"
            )

    def measure(self, seconds: float, tracer) -> dict:
        result = train_steps_trinity.Driver.measure(self, seconds, tracer)
        result["facts"]["mixers"] = dict(
            self.mixers, lowering=self.attention, flash_calls=self.flash_calls
        )
        return result
