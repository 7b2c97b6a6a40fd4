"""Traffic driver ``train_steps_nemotron3``: the closed loop of ``train_steps``
(steps back to back, one queued ahead, the window ends on the last loss)
over the Nemotron-H hybrid block of ``accl_tpu.models`` as ONE chip of its
8-way expert-parallel group computes it: blocks of ONE sub-layer each, five
Mamba-2 mixers (the chunked selective state-space recurrence with a scalar
decay a head, ``accl_tpu/ops/ssd.py``) and five LatentMoE layers (a sigmoid
router with a selection bias, top 22 of 512, non-gated relu2 experts that
work in a 1,024-wide latent, a shared expert on the hidden state, 64
experts held) to one grouped-query attention block without position;
through ``make_sharded_train_step`` on a world of one chip.

Set-up builds the program's config FIRST, so a tree whose
``TransformerConfig`` lacks the block fails at once.  The weights are the
seed's; the expert bias is then brought to balance by a fixed number of
rounds of its own rule (``balanced``).  Then the check, on the first batch,
against the plain float32 reference in ``perfbench/reference/
nemotron_h.py`` (Mamba-2 as the token-by-token recurrence; given the same
held range; a block at a time, so that one block's float32 weights are
alive at once):

* (a) the router's counters through ``make_sharded_router_probe``: tokens
  an expert a block over all 512 and the entries held here against the
  reference's, both within the count of near-tie tokens; nothing dropped;
* (b) logits of the batch's first sequence through ``make_sharded_forward``,
  its LAST and its FIRST ``check_positions`` positions (late: 63 chunks of
  carried state; early: the convolutions' padding and ``S_0``): a row's
  relative error at its median, and all the rows' relative RMS and largest
  error (why not the rows clear of a near-tie: the limits' comment below);
* (c) the loss the FIRST train step returns (through
  ``make_sharded_train_step`` itself) against the reference's of the batch;
* (d) the UPDATE, against the reference's gradients (taken a block at a
  time, last block first): what the first step of the compiled step the
  window times did to every leaf, as far as bf16 SGD at the cell's rate
  shows a gradient at all, and what the same step compiled at
  UPDATE_PROBE_RATE did, where every leaf shows it (``_moved``; the limits'
  comment says what each reading can and cannot see).

``setup`` is ``prepare`` (the program's side: logits, counters, the two
steps' losses and updated weights, kept on the host), ``judge`` (the
reference's side and the comparison) and ``warm_up``;
``perfbench/controls_nemotron3.py`` plants faults through ``judge``'s
arguments, and each has to end not correct.
"""

from __future__ import annotations

import math

import numpy as np

from perfbench import flops_nemotron3
from perfbench.drivers import train_steps_trinity
from perfbench.drivers.train_steps_ling3 import scoped_instructions
from perfbench.drivers.train_steps_trinity import balanced, held_entries
from perfbench.reference import nemotron_h as reference

#: Limits of the check: the program (bf16 weights and activations, f32
#: accumulation, the SSD core's float32 products in one bf16 pass, f32
#: router sigmoid) against the float32 reference at "highest" matmul
#: precision.  Measured on the v5e at the published widths and eleven
#: blocks (my chip runs, PR 45: (a)-(c) 23 runs at 23 seeds, ten before
#: their limits were set and thirteen after; (d) at the end of this comment).
#:
#: ROUTING NEAR-TIES.  bf16 rounding of the hidden state moves a selection
#: score (``sigmoid + bias``, float32, a dot product over 4,096 columns) by
#: a fraction of a bf16 spacing of the scores' size, which can swap a
#: token's 22nd and 23rd expert where the float32 reference does not.  With
#: 22 of 512 the scores are DENSE: 20-43% of a block's 8,192 tokens have
#: that gap within a QUARTER of a bf16 spacing (2^-8 of the block's score
#: RMS; ``reference.routing_facts``; 1,647-1,796 tokens in the first expert
#: block, 2,913-3,490 in the last), and 4.2-12% of a block's tokens do swap
#: an expert (346-401 entries moved in the first expert block, 846-1,014 in
#: the last).
#: A token is NEAR A TIE where the gap is within NEAR_TIE_SPACINGS.  (a) Half
#: the L1 distance between the program's tokens-an-expert histogram over all
#: 512 and the reference's must stay under that block's count of near-tie
#: tokens (a swapped expert moves ONE of a token's entries: no group limit
#: here): the largest ratio read is 0.35; and so must the difference in the
#: entries HELD here (read 19-109 against those allowances); no entry
#: dropped (the held entries a block read 19,625-25,600 of the buffer's
#: 45,056 rows).
#:
#: (b) LOGITS, ALL THE CHECKED ROWS, as the Ling-3.0 cell and for its reason:
#: a swapped token's hidden state enters the next three tokens' x, B and C
#: through the convolutions and every later token through the state, in each
#: Mamba-2 block that follows, so no row is clear of its neighbours' swaps.
#: The statistics a cascade of swaps bounds and a wrong computation does not
#: pass: a row's relative error (L2 over the vocabulary) at its MEDIAN,
#: 0.68-0.90%; the rows' relative RMS, 2.41-2.84%; the largest error,
#: 0.58-0.79 on logits of RMS 1.47 (a row whose own expert swapped late).
#: They are a TENTH of Ling-3.0's (6-9%): a swap here moves one of 22
#: entries whose weights sum to 5, there one of 8 or a whole group.  Early
#: and late rows read alike (medians 0.66-0.94% and 0.64-1.18%).
#: (c) The first step's loss against the reference's: 1.4e-6 to 7.3e-5
#: apart.
#:
#: WHICH SCALE KEEPS WHAT ALIVE (ISSUE 45 asked: at 0.02 ``relu(.) ** 2`` of
#: a 1,024-wide latent may vanish under bf16).  The matrices' scales are the
#: program's own (``init_params`` 0.02 for the mixers, ``init_moe_params``
#: fan_in^-0.5 for the expert blocks; the file's ``departures``) and nothing
#: was rescaled: the reference's RMS of what each block ADDS over the RMS of
#: the stream it adds to (``blocks_added``, every run prints it), in the
#: blocks' order MEMEMEMEM*E: 92.2-92.8 (the first Mamba-2 block on the 0.02
#: embedding: from there on the stream is the blocks', not the table's),
#: 0.86-0.87, 0.73-0.75, 0.56-0.59, 0.46-0.48, 0.43-0.47, 0.34-0.36,
#: 0.36-0.39, 0.27-0.28, 0.22-0.27 (attention), 0.29-0.31.  No block is dead and none swamps the stream; the
#: expert blocks' relu2 bank at fan_in^-0.5 adds as much as a mixer.  The
#: controls below say the same of the parts: the decay, the convolution, the
#: skip ``D``, the square and the latent each move the logits by 18-60%.
#:
#: CONTROLS of (a)-(c), each at two seeds (1618033989, 4000000007; my chip
#: runs, PR 45, read BEFORE these limits were set, by a script that went;
#: ``perfbench/controls_nemotron3.py`` has since judged the same faults by
#: ``judge`` at the limits below: (d)), as median row, relative RMS, largest
#: error, loss apart, entries moved in the last expert block against its
#: allowance.  The
#: nearest precision below bf16, the reference from weights rounded to fp8
#: (e5m2, ``lax.reduce_precision``): 53.9-55.0%, 54.1-55.2%, 3.16-3.18,
#: 1.23e-3 to 2.26e-3, 68,569-71,426 against 2,174-2,235: past every limit.
#: The reference without the decay (``A = 0``): 57.1-58.7%, 56.9-58.3%,
#: 4.59-4.64, 5.1e-4 to 2.7e-3, 95,697-96,571.  Without the convolutions:
#: 58.7-59.8%, 58.8-59.9%, 4.74-4.94, 9.5e-4 to 1.6e-3, 104,912-106,837.
#: ``relu`` for ``relu ** 2``: 30.0-30.6%, 30.1-30.7%, 2.41-2.44, 3.7e-4 to
#: 4.9e-4, 42,576-43,258.  Without ``W_down`` / ``W_up``: 20.3-20.9%,
#: 20.6-21.2%, 2.15-2.28, 5.8e-4 to 7.4e-4, 32,494-34,783.  Without ``D``:
#: 26.2-26.8%, 27.0-27.6%, 4.68-5.55, 3.8e-5 (passes) to 6.1e-4,
#: 15,900-17,768.  The gate AFTER the grouped norm: 18.1-18.2%, 18.4-18.5%,
#: 1.68-1.69, 2.4e-4 (passes) to 3.2e-4, 19,391-22,592.  Every control is
#: past the median row's, the relative RMS's and the largest error's limit
#: and past the routing's allowance by 5 to 40 times; two of fourteen pass
#: the loss (uniform ids at seeded weights hardly see a mixer's detail).
#: Each limit is the geometric mean of its two readings, the largest the
#: change gave over its seeds and e5m2's smallest: 0.885% and 53.9% (7.9 and
#: 7.7 times of room), 2.83% and 54.1% (4.2 and 4.5), 0.757 and 3.16 (2.0 and
#: 2.1), 5.3e-5 and 1.23e-3 (4.7 and 4.9); they were set from the first ten
#: seeds and the thirteen runs after them were not tuned on (over all 23 the
#: largest are 0.90%, 2.84%, 0.794 and 7.3e-5: 7.8, 4.2, 1.9 and 3.4 times
#: of room).
#:
#: (d) THE UPDATE.  (a)-(c) see the forward pass alone: a wrong gradient or a
#: state left unchanged passes them.  What the step did to the weights is
#: held against ``-lr`` times the reference's gradient, leaf by leaf, twice,
#: because bf16 SGD at the cell's rate shows little: ``p - 0.001 g`` rounds
#: back to ``p`` wherever ``0.001 |g|`` is under half a spacing of ``p``, and
#: a bf16 normal draw has 256 values, none nearer zero than 9.8e-5 at std
#: 0.02.  The timed step changes 0.002-0.4% of a Mamba-2 or attention
#: matrix's, the shared expert's, the table's and the head's elements and
#: NOT ONE of the routed experts', the router's, ``W_down`` / ``W_up``'s,
#: ``wq`` / ``wk``'s, the convolutions' or the norms' (the update over all a
#: leaf's elements is 0.996-1.000 of ``lr g`` away from it: a first reading
#: of 1 is rounding here, in the program as ISSUE 45 asked for it, plain SGD
#: on bf16 weights); its float32 leaves (``a_log``, ``dt_bias``, ``d_skip``,
#: 128 each) move by a few float32 spacings.  So:
#: THE TIMED STEP, as far as it shows: an element is IN PLAY where the step
#: changed it or where ``-lr g``, UPDATE_TOLERANCE more or less, takes it to
#: another value of its type; the reading is the share of a leaf's elements
#: in play left at a value that no such update rounds to (leaves with
#: UPDATE_MIN_IN_PLAY or more: 48-53 of the tree's 133, every Mamba-2 block's
#: ``a_log``, ``d_skip``, ``wdt``, ``wo``, ``wx``, ``wz``, the shared
#: experts, attention's ``wo`` and ``wv``, the table, the head); the worst
#: leaf reads 0.042-0.116 over nine seeds (a Mamba-2 block's ``a_log`` or
#: ``d_skip`` of 60-120 elements in play, of which 4-12 are off), a state
#: left unchanged 0.96-0.98, e5m2 weights 0.80-0.83, the seven wrong
#: references 0.63-1.0.
#: THE PROBE STEP, every leaf: ``make_sharded_train_step`` once more at
#: UPDATE_PROBE_RATE, which makes the update the gradient to bf16's 2^-9;
#: its compiled text is the timed step's with that one constant changed
#: (``tests/test_chip_compile.py`` holds them to each other on the described
#: chip) and its loss is the timed step's to the bit.  The reading is a
#: leaf's ``|probed - before + rate g| / |rate g|``: 0.011-0.06 on the
#: mixers', the shared experts' and the head's leaves, 0.11-0.33 on the
#: routed experts', the latent's and the router's (their rows are the
#: routing's: 4-12% of a block's tokens swap an expert against float32), the
#: worst always a late block's router, 0.270-0.374 over nine seeds; a state
#: left unchanged 1.0 on every leaf, e5m2 weights 1.14-1.23 (no leaf under
#: 0.34), the wrong references 1.05-22.
#: Limits: the timed step's between 0.116 and 0.96 with the more room above
#: the reading (2.6 and 3.2 times), the probe's the geometric mean of 0.374
#: and 1 (1.6 and 1.7 times: the reading's spread over nine seeds is 0.03).
#: Seeds 3000000019 and 3000000041 were read while (d) was built; 2971215091
#: and 1134903217 (with all eight controls, each not correct at both:
#: ``unchanged_state`` by (d)'s two limits alone, the others by (b)'s too) and
#: 3524578003, 2178309011, 1346269013, 4181000017, 2584000009 after the
#: limits were set: all nine correct (my chip runs, PR 45).  NOT judged: the
#: expert bias's move by its rule (outside the gradient); the timed step's
#: update of the leaves it does not change.
ROW_MEDIAN_LIMIT = 0.07
REL_RMS_LIMIT = 0.12
MAX_ABS_LIMIT = 1.5
LOSS_REL_LIMIT = 2.5e-4
NEAR_TIE_SPACINGS = 0.25
UPDATE_TOLERANCE = 0.1
UPDATE_MIN_IN_PLAY = 32
UPDATE_TIMED_LIMIT = 0.3
UPDATE_PROBE_RATE = 4096.0
UPDATE_PROBE_LIMIT = 0.6
#: what a run prints beside what it is judged by, for the next look: the
#: count of near-tie tokens at other margins, a row's error at quantiles
_NEAR_TIE_LOOK = (0.125, 0.25, 0.5, 1.0, 2.0)
_ROW_LOOK = (0.1, 0.5, 0.9, 0.99, 1.0)

#: The rate of each round of the expert bias's rule that set-up runs before
#: anything is checked or timed (the configuration file's ``departures``
#: says why; ``train_steps_trinity``'s schedule): a forward pass over every
#: token batch a round.
BALANCE_RATES = (0.02,) * 4 + (0.01,) * 4 + (0.005,) * 4


def layer_letters(config: dict) -> str:
    """``flops_nemotron3.layer_letters`` (the letter of each block kept, from
    its PUBLISHED index), of a file whose ``layers_kept`` lists as many
    blocks as it says it has."""
    if len(config["layers_kept"]) != config["num_hidden_layers"]:
        raise ValueError("layers_kept does not list num_hidden_layers blocks")
    return flops_nemotron3.layer_letters(config)


def program_config(config: dict):
    """The published keys as ``accl_tpu.models.TransformerConfig``."""
    import jax.numpy as jnp

    from accl_tpu.models import Mamba2, TransformerConfig, hybrid_layers

    program, assumed = config["program"], config["assumed"]
    if (config["model_type"], config["mlp_hidden_act"],
            config["mamba_hidden_act"]) != ("nemotron_h", "relu2", "silu"):
        raise ValueError("the block is nemotron_h's: relu2 MLPs, SiLU in Mamba-2")
    if (
        config["n_group"] != 1 or config["topk_group"] != 1
        or not config["use_conv_bias"] or config["mamba_proj_bias"]
        or config["mlp_bias"] or config["attention_bias"] or config["use_bias"]
        or config["num_nextn_predict_layers"] or config["residual_in_fp32"]
        or config["sliding_window"] is not None
        or config["mamba_num_heads"] * config["mamba_head_dim"]
        != config["expand"] * config["hidden_size"]
        or config["layer_norm_epsilon"] != config["norm_eps"]
    ):
        raise ValueError(
            "the variant is the router without a group limit, a bias on the "
            "convolution alone, no prediction module, the stream in the "
            "weights' type, no window, and d_inner = expand x hidden_size"
        )
    held, of = config["n_routed_experts"], config["num_router_experts"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        layers=hybrid_layers(
            layer_letters(config), d_ff=config["intermediate_size"],
            moe_d_ff=config["moe_intermediate_size"],
        ),
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]
        ],
        # no block rotates (``hybrid_layers``): "rope" only says that the
        # tree holds no position table
        pos_embedding="rope",
        rope_base=float(config["rope_theta"]),
        norm="rmsnorm",
        norm_eps=float(config["layer_norm_epsilon"]),
        ffn="relu2",
        tie_head=config["tie_word_embeddings"],
        mamba=Mamba2(
            n_heads=config["mamba_num_heads"],
            head_dim=config["mamba_head_dim"],
            state=config["ssm_state_size"],
            groups=config["n_groups"],
            conv=config["conv_kernel"],
            chunk=config["chunk_size"],
            dt_min=float(config["time_step_min"]),
            dt_max=float(config["time_step_max"]),
            dt_floor=float(config["time_step_floor"]),
        ),
        n_experts=held,
        moe_top_k=config["num_experts_per_tok"],
        moe_capacity_factor=None,
        moe_norm_topk_prob=config["norm_topk_prob"],
        moe_aux_weight=0.0,
        moe_router_z_weight=0.0,
        moe_router="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_bias_rate=float(assumed["expert_bias_update"]),
        moe_shared_d_ff=config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        moe_latent=config["moe_latent_size"],
        moe_router_experts=None if of == held else of,
        moe_first_expert=config["first_expert"],
        moe_held_row_factor=float(program["held_row_factor"]),
        attention=program["attention"],
        remat=program["remat"],
    )


def reference_model(config: dict) -> dict:
    """The keyword arguments ``reference.layer`` takes, from the keys."""
    return dict(
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"],
        ssm_state_size=config["ssm_state_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        top_k=config["num_experts_per_tok"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        first_expert=config["first_expert"],
    )


def reference_block(lp: dict) -> dict:
    """One block of the program's parameter tree under the reference's
    names: a Mamba-2 block's five input matrices side by side as
    ``in_proj`` ([z | x | B | C | dt]) and its taps and biases as one
    ``conv1d`` ([x | B | C]); the held experts' matrices stacked on a
    leading axis."""
    import jax.numpy as jnp

    side = lambda names: jnp.concatenate([lp[n] for n in names], axis=-1)
    if "moe" in lp:
        moe = lp["moe"]
        return {
            "norm": lp["ln2"],
            "gate": moe["gate"], "expert_bias": moe["bias"],
            "fc1_latent_proj": moe["w_down"],
            "fc2_latent_proj": moe["w_up"],
            "experts.up_proj": moe["w1"], "experts.down_proj": moe["w2"],
            "shared_experts.up_proj": moe["shared"]["w1"],
            "shared_experts.down_proj": moe["shared"]["w2"],
        }
    if "d_skip" in lp:
        return {
            "norm": lp["ln1"],
            "in_proj": side(("wz", "wx", "wb", "wc", "wdt")),
            "conv1d": side(("conv_x", "conv_b", "conv_c")),
            "conv1d_bias": side(("bias_x", "bias_b", "bias_c")),
            "dt_bias": lp["dt_bias"], "A_log": lp["a_log"],
            "D": lp["d_skip"], "mixer_norm": lp["y_norm"],
            "out_proj": lp["wo"],
        }
    return {
        "norm": lp["ln1"], "q_proj": lp["wq"], "k_proj": lp["wk"],
        "v_proj": lp["wv"], "o_proj": lp["wo"],
    }


def reference_top(params: dict) -> dict:
    """The tree's leaves outside the blocks under the reference's names."""
    return {
        "embeddings": params["embed"],
        "norm_f": params["ln_f"],
        "lm_head": params["head"],
    }


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the reference's names."""
    return dict(
        reference_top(params),
        layers=[reference_block(lp) for lp in params["layers"]],
    )


def fp8(tree):
    """``tree`` with every leaf rounded to fp8 (e5m2, the nearest precision
    below bf16) but the expert bias, which the reference adds in float32."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map_with_path(
        lambda path, p: p if path[-1].key == "bias" else jax.lax.reduce_precision(
            p.astype(jnp.float32), 5, 2
        ).astype(p.dtype),
        tree,
    )


class Driver(train_steps_trinity.Driver):
    """``_segment`` and ``_note_loss`` are ``train_steps``'; ``measure``
    adds the mixers' facts to ``train_steps_trinity``'s."""

    def setup(self) -> None:
        self.prepare()
        self.judge()
        self._mark("reference_check")
        self.warm_up()

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
            init_params,
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
        letters = layer_letters(self.config)
        self.mixers = {
            "mamba_layers": letters.count("M"),
            "attention_layers": letters.count("*"),
            "expert_layers": letters.count("E"),
            "ssd_chunk": cfg.mamba.chunk,
        }

        q = jax.ShapeDtypeStruct(
            (B, cfg.n_heads, T, cfg.head_size()), jnp.dtype(cfg.dtype)
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
            lambda k: init_params(k, cfg), out_shardings=shardings
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
        # what its update shows of the gradient (the limits' comment)
        probe_step, _ = make_sharded_train_step(cfg, mesh, lr=UPDATE_PROBE_RATE)
        params, loss = probe_step.lower(
            params, self.tokens[0], self.targets[0]
        ).compile()(params, self.tokens[0], self.targets[0])
        self.got["probe_loss"] = float(loss)
        self.probed = jax.device_get(params)
        del probe_step
        params = jax.device_put(self.before, shardings)
        self._mark("probe_step")

        # compiled ONCE, ahead of time: nothing can compile in the window
        step, _ = make_sharded_train_step(cfg, mesh, lr=float(tr["lr"]))
        self.step = step.lower(params, self.tokens[0], self.targets[0]).compile()
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
        # off the device while the reference works there: a block of it
        # backwards in float32 beside 5.5 GB of weights would not fit
        self.after = jax.device_get(params)
        self._shardings = shardings
        self._mark("first_step")

    def warm_up(self) -> None:
        import jax

        self.params = jax.device_put(self.after, self._shardings)
        del self.before, self.after, self.probed, self.got
        n = len(self.tokens)
        self.params, loss = self.step(
            self.params, self.tokens[1 % n], self.targets[1 % n]
        )
        self._note_loss(float(loss))
        self._mark("step_warm_up")

    # -- the check -----------------------------------------------------------

    def _reference(self, rows, fp8_weights: bool = False,
                   unchanged_state: bool = False, **how):
        """The reference on the first batch from the weights as they were
        before the first step, a block at a time, forwards and then
        backwards: logits of the first sequence's positions ``rows``, the
        loss, each expert block's routing facts, what each block adds to
        the stream (the RMS of ``f`` over the RMS of ``h``, for the look at
        which scale keeps what alive), and the gradients, which
        ``_moved`` holds against the two steps' updates leaf by leaf, in
        the program's names.  ``fp8_weights``: the reference from weights
        rounded to e5m2; ``how``: keyword arguments that break a block (the
        controls of ``perfbench/controls_nemotron3.py``), as is
        ``unchanged_state``: the weights before the step in the place of
        those after it."""
        import jax
        import jax.numpy as jnp

        model = dict(reference_model(self.config), **how)
        top_k = self.cfg.moe_top_k
        tokens, targets = self.tokens[0], self.targets[0]
        f32 = lambda tree: jax.tree.map(lambda p: p.astype(jnp.float32), tree)
        rounded = fp8 if fp8_weights else (lambda tree: tree)
        block = lambda h, lp: reference.layer(
            h, reference_block(lp), q_block=min(512, self.T), **model
        )

        @jax.jit
        def one_layer(h, lp):
            with jax.default_matmul_precision("highest"):
                new, picked = block(h, rounded(lp))
            rms = lambda x: jnp.sqrt(jnp.mean(jnp.square(x)))
            added = rms(new - h) / rms(h)
            if picked is None:
                return new, added, None
            return new, added, reference.routing_facts(picked, top_k)

        @jax.jit
        def one_layer_back(h, lp, d_out):
            with jax.default_matmul_precision("highest"):
                _, back = jax.vjp(
                    lambda h, lp: block(h, lp)[0], h, f32(rounded(lp))
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
                    {"embeddings": table["embed"]}, tokens
                ),
                f32(rounded(table)),
            )
            return back(d_h)[0]

        top = jax.device_put(
            {k: v for k, v in self.before.items() if k != "layers"}
        )
        h = jax.jit(
            lambda top: reference.embed(reference_top(rounded(top)), tokens)
        )(top)
        facts, added, inputs = [], [], []
        for lp in self.before["layers"]:
            inputs.append(h)
            h, block_added, layer_facts = one_layer(h, jax.device_put(lp))
            added.append(float(block_added))
            if layer_facts is not None:
                facts.append(layer_facts)
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
            d_h, d_lp = one_layer_back(inputs.pop(), lp, d_h)
            moved.update(self._moved(
                f"{i}.", lp, *stepped(lp, lambda tree: tree["layers"][i]), d_lp
            ))
            del d_lp                # a block's float32 gradients: 1.6 GB
        d_top.update(embed_back({"embed": top["embed"]}, d_h))
        moved.update(self._moved(
            "", top, *stepped(top, lambda tree: {k: tree[k] for k in top}),
            d_top,
        ))
        self.blocks_added = added
        return np.asarray(want), float(loss), facts, moved

    def _moved(self, prefix: str, before, after, probed, grads) -> dict:
        """What the two steps did to each leaf against the reference's
        gradient ``grads``, by leaf name (the limits' comment says why two
        readings).  The timed step, ``after``: the elements IN PLAY (those
        it changed, and those that ``-lr grad``, a tenth more or less, takes
        to another value of the leaf's type) and, of them, those it left at
        a value that no ``-lr grad`` within that tenth rounds to.  The
        probe step, ``probed``: the sums of ``(probed - before + rate grad)
        ** 2`` and ``(rate grad) ** 2``."""
        import jax
        import jax.numpy as jnp

        lr = float(self.traffic["lr"])

        def leaf(w, new, far, grad):
            kind = jnp.finfo(w.dtype)
            # a float32 number rounded to the leaf's type; spelled so,
            # because a cast there and back is one the compiler may drop
            stored = lambda x: jax.lax.reduce_precision(x, kind.nexp, kind.nmant)
            w, new, far = (x.astype(jnp.float32) for x in (w, new, far))
            d = -lr * grad
            ends = d * (1 - UPDATE_TOLERANCE), d * (1 + UPDATE_TOLERANCE)
            low = stored(w + jnp.minimum(*ends))
            high = stored(w + jnp.maximum(*ends))
            play = (low != w) | (high != w) | (new != w)
            count = lambda x: jnp.sum(x, dtype=jnp.float32)
            return jnp.stack([
                count(play), count(play & ~((low <= new) & (new <= high))),
                jnp.sum((far - w + UPDATE_PROBE_RATE * grad) ** 2),
                jnp.sum((UPDATE_PROBE_RATE * grad) ** 2),
            ])

        sums = jax.device_get(jax.jit(
            lambda *trees: jax.tree.map(leaf, *trees)
        )(before, after, probed, grads))
        return {
            prefix + ".".join(k.key for k in path): [float(v) for v in s]
            for path, s in jax.tree_util.tree_leaves_with_path(sums)
        }

    def _checked_rows(self):
        """The first sequence's positions whose logits are compared: its
        first ``check_positions`` and its last (all of it where those
        overlap)."""
        span = min(int(self.traffic["check_positions"]), self.T // 2)
        return np.concatenate(
            [np.arange(span), np.arange(self.T - span, self.T)]
        ), span

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
        # histograms is a lower bound on such entries, a block
        moved_entries = np.abs(counts - want_counts).sum(axis=1) // 2
        held_off = np.abs(here - want_here)
        entries = counts.sum(axis=1)
        # by leaf: the timed step's share of elements in play left where no
        # rounding of the reference's update puts them; the probe step's
        # update off the reference's
        # (but the expert bias: outside the gradient, moved by its rule)
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
            "dropped": dropped,
            "moved_entries": moved_entries.tolist(),
            "held_entries": here.tolist(),
            "reference_held_entries": want_here.tolist(),
            "allowed_entries": allowed.tolist(),
            "near_ties": {
                str(m): near[i].tolist() for i, m in enumerate(_NEAR_TIE_LOOK)
            },
            # the reference's RMS of what each block adds over the RMS of
            # the stream it adds to, in the blocks' order
            "blocks_added": self.blocks_added,
            "update_timed_worst": timed.get(worst(timed)),
            "update_timed_worst_leaf": worst(timed),
            "update_timed_leaves": len(timed),
            "update_probe_worst": probed.get(worst(probed)),
            "update_probe_worst_leaf": worst(probed),
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
                f"tokens an expert: {moved_entries.tolist()} entries a block "
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
        loops = None
        if tracer.enabled:
            loops = scoped_instructions(self.step.as_text())
        result = super().measure(seconds, tracer)
        result["facts"]["mixers"] = self.mixers
        if loops is not None:
            # the new scopes' instructions, loop bodies and all (the SSD
            # core's scan over the chunks is a loop)
            result["facts"]["scope_ops_all"] = {
                s: names for s, names in loops.items()
                if s in ("accl.attn::ssd", "accl.attn::mamba_proj",
                         "accl.moe::latent")
            }
        return result
