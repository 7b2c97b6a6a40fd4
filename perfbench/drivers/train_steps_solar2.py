"""Traffic driver ``train_steps_solar2``: the closed loop of ``train_steps``
(steps back to back, one queued ahead, the window ends on the last loss)
over the Solar Open 2 hybrid block of ``accl_tpu.models`` as ONE chip of its
8-way expert-parallel group computes it: one grouped-query softmax layer
without position whose output a sigmoid gates a channel, then three KDA
linear-attention layers (a chunked gated delta rule with a decay a channel,
``accl_tpu/ops/kda.py``) under the PUBLISHED decay gate, ``-exp(A_log)
softplus(.)`` with no lower bound (the core splits its decays by halving),
gate projections through rank 128 and a write strength in (0, 2); every
layer an expert layer under a sigmoid router with a selection bias, top 8 of
320 with a shared expert, 40 experts held; through
``make_sharded_train_step`` on a world of one chip.

Set-up builds the program's config FIRST, so a tree whose
``TransformerConfig`` lacks the block fails at once (the parent of PR 48:
``DeltaAttention`` has no ``beta_scale``).  The weights are the seed's; the
expert bias is then brought to balance by a fixed number of rounds of its
own rule (``balanced``).  Then the check, on the first batch, against the
plain float32 reference in ``perfbench/reference/solar_open2.py`` (KDA as
the token-by-token recurrence; given the same held range; a layer at a time,
so that one layer's float32 weights are alive at once):

* (a) the router's counters through ``make_sharded_router_probe``: tokens
  an expert a layer over all 320 and the entries held here against the
  reference's, both within the count of near-tie tokens; nothing dropped;
* (b) logits of the batch's first sequence through ``make_sharded_forward``,
  its LAST and its FIRST ``check_positions`` positions (late: 127 chunks of
  carried state; early: the convolutions' padding and ``S_0``): a row's
  relative error at its median, and all the rows' relative RMS and largest
  error (why not the rows clear of a near-tie: the limits' comment below);
* (c) the loss the FIRST train step returns (through
  ``make_sharded_train_step`` itself) against the reference's of the batch;
* (d) the UPDATE, against the reference's gradients (taken a layer at a
  time, last layer first): what the first step of the compiled step the
  window times did to every leaf, as far as bf16 SGD at the cell's rate
  shows a gradient at all, and what the same step compiled at
  UPDATE_PROBE_RATE did, where every leaf shows it (``_moved``; as
  ``train_steps_nemotron3``, whose ``prepare`` / ``judge`` / ``_moved`` these
  are copies of: its limits' comment says what each reading can and cannot
  see).

The reference's pass also reads the first batch's log-decays, layer by
layer (``reference.gate_facts``): the run's ``kda_gate_unbounded_share``
says how much of the gate lies past Ling-3.0's bound and how many runs of 16
tokens sum past float32's range, that is, whether the cell still works the
path it is for.

``setup`` is ``prepare`` (the program's side: logits, counters, the two
steps' losses and updated weights, kept on the host), ``judge`` (the
reference's side and the comparison) and ``warm_up``;
``perfbench/controls_solar2.py`` plants faults through ``judge``'s
arguments, and each has to end not correct.
"""

from __future__ import annotations

import math

import numpy as np

from perfbench import flops_solar2
from perfbench.drivers import train_steps_trinity
from perfbench.drivers.train_steps_ling3 import scoped_instructions
from perfbench.drivers.train_steps_nemotron3 import fp8
from perfbench.drivers.train_steps_trinity import balanced, held_entries
from perfbench.reference import solar_open2 as reference

#: Limits of the check: the program (bf16 weights and activations, f32
#: accumulation, the KDA core's float32 products in one bf16 pass, f32 router
#: sigmoid) against the float32 reference at "highest" matmul precision.
#: Measured on the v5e at the published widths and four layers (my chip runs,
#: PR 48: seven seeds BEFORE these limits were set, the cell at 3000000019,
#: 1618033989, 4000000007, 2971215091, 1134903217, 3524578003 and the controls'
#: set-up at 2178309011; the runs after them are in ``PERF.md`` section 4).
#:
#: ROUTING NEAR-TIES, as the other held cells: bf16 rounding of the hidden
#: state can swap a token's 8th and 9th expert where the float32 reference
#: does not.  A token is NEAR A TIE where that gap is within NEAR_TIE_SPACINGS
#: bf16 spacings (2^-8) of the layer's score RMS: 780-1,402 of a layer's 8,192
#: tokens.  (a) Half the L1 distance between the program's tokens-an-expert
#: histogram over all 320 and the reference's must stay under that count:
#: 200-235 entries moved in the first layer, 578-660 in the last, at most 0.58
#: of the allowance; so must the difference in the entries HELD here (1-54);
#: none dropped (7,531-9,849 held a layer of the buffer's 16,384 rows).
#:
#: (b) LOGITS, ALL THE CHECKED ROWS, as the Ling-3.0 and Nemotron-3 cells and
#: for their reason: a swapped token's hidden state enters the next three
#: tokens' q, k and v through the convolutions and every later token through
#: the state, and through the softmax layer's keys and values.  A row's
#: relative error (L2 over the vocabulary) at its MEDIAN 2.36-2.73%; the rows'
#: relative RMS 3.25-3.74%; the largest error 0.53-0.73 on logits of RMS 1.38.
#: Early and late rows read alike (medians 2.56-3.17% and 2.03-2.82%).  Three
#: times Nemotron-3's readings, a third of Ling-3.0's: a swap here moves one
#: of 8 entries whose weights sum to 1.  (c) The first step's loss against the
#: reference's: 7.8e-6 to 8.6e-5 apart.
#:
#: WHICH SCALE KEEPS WHAT ALIVE: the reference's RMS of what each layer ADDS
#: over the RMS of the stream it adds to (``blocks_added``), G K K K:
#: 32.6-33.7 (the first layer on the 0.02 embedding), 2.00-2.10, 0.82-0.84,
#: 0.61-0.62: no layer is dead and none swamps the stream.  THE GATE
#: (``kda_gates``, the first batch's log-decays a KDA layer): 31.4-32.1% of
#: (token, channel) values under Ling-3.0's bound of -5, 30.4-31.1% of (run of
#: 16 tokens, channel) sums past -88, where a split at a sub-block's middle
#: leaves float32, 8.2-9.4% of (chunk, channel) sums above -1; quantiles 0, 1,
#: 10, 50, 90, 99%: -267, -171, -45, -0.94, -0.018, -0.0036.  A run with no
#: sub-block past -88 is not correct: it would not work the path it times.
#:
#: CONTROLS (``perfbench/controls_solar2.py``, seed 2178309011, through
#: ``judge`` itself; read at Nemotron-3's limits, before these were set), as
#: median row, relative RMS, largest error, loss apart, entries moved in the
#: last layer against its allowance, the timed step's worst leaf, the probe
#: step's: the sound reference 2.36%, 3.25%, 0.59, 2.9e-5, 578 / 1,161, 0.179,
#: 0.378.  The nearest precision below bf16, the reference from weights
#: rounded to e5m2: 79.6%, 79.4%, 5.87, 1.37e-3, 27,822 / 773, 0.953, 1.41.
#: Ling-3.0's BOUNDED gate in softplus' place: 78.3%, 76.2%, 6.00, 6.4e-4,
#: 22,811 / 774, 0.94, 1.36.  beta without its 2: 24.8%, 24.6%, 2.01, 2.3e-4
#: (passes), 11,595 / 1,359, 0.81, 1.24: the NEAREST control.  No decay: 96.6%,
#: 93.5%, 7.18, 1.7e-3, 28,515 / 690, 1.0, 1.35.  No convolution: 75.5%,
#: 75.6%, 6.13, 2.0e-4 (passes), 42,687 / 1,227, 1.0, 4.06.  The GQA gate left
#: out: 63.2%, 63.2%, 4.94, 7.0e-4, 5,101 / 1,135, 1.0, 1.37.  Rope on the GQA
#: layer: 88.2%, 87.3%, 6.58, 4.8e-4, 4,551 / 1,185, 0.93, 1.52.  A state left
#: unchanged: (a)-(c) the sound reference's, 0.98, 1.0.
#: Each of (b)'s limits is the geometric mean of the largest reading the change
#: gave over its seven seeds and the NEAREST control's (beta without its 2,
#: three times nearer than e5m2): 2.73% and 24.8% (2.9 and 3.1 times of room),
#: 3.74% and 24.6% (2.5 and 2.6), 0.730 and 2.01 (1.64 and 1.67); e5m2 is 10,
#: 8 and 4.9 times past them.  (c)'s is the geometric mean of 8.6e-5 and e5m2's
#: 1.37e-3 (4.1 and 3.9 times): uniform ids at seeded weights hardly see a
#: mixer's detail, and two controls pass it.
#:
#: (d) THE UPDATE, as ``train_steps_nemotron3`` (its comment says what each of
#: the two readings can and cannot see).  THE TIMED STEP as far as bf16 SGD at
#: lr 0.001 shows it: 44-45 of the tree's 89 leaves have UPDATE_MIN_IN_PLAY
#: elements in play (not one of the routed experts', the routers', the taps',
#: the norms' or ``wf_a`` / ``wf_b``'s); the worst leaf is always a KDA
#: layer's ``a_log`` (64 numbers, 45-57 in play, 4-10 of them off): 0.070-0.179
#: over seven seeds; a state left unchanged 0.98, e5m2 0.95, the nearest
#: control 0.81.  THE PROBE STEP, every leaf's ``|probed - before + rate g| /
#: |rate g|``: 0.02-0.06 on the mixers', the shared experts' and the head's
#: leaves, 0.30-0.31 on a late layer's routed experts', the worst always a
#: late layer's router, 0.364-0.436; a state left unchanged 1.0 on every leaf,
#: e5m2 1.41, the wrong references 1.24-4.1.  Limits: the timed step's between
#: 0.179 and 0.81 (2.2 and 2.0 times; the reading is a count of 4-10 in about
#: 50, so fresh seeds spread), the probe's between 0.436 and 1, with the more
#: room above the reading (1.6 and 1.43 times: an unchanged state reads 1.0
#: exactly).  NOT judged: the expert bias's move by its rule (outside the
#: gradient); the timed step's update of the leaves it does not change.
ROW_MEDIAN_LIMIT = 0.08
REL_RMS_LIMIT = 0.095
MAX_ABS_LIMIT = 1.2
LOSS_REL_LIMIT = 3.5e-4
NEAR_TIE_SPACINGS = 0.25
UPDATE_TOLERANCE = 0.1
UPDATE_MIN_IN_PLAY = 32
UPDATE_TIMED_LIMIT = 0.4
UPDATE_PROBE_RATE = 4096.0
UPDATE_PROBE_LIMIT = 0.7
#: what a run prints beside what it is judged by, for the next look: the
#: count of near-tie tokens at other margins, a row's error at quantiles
_NEAR_TIE_LOOK = (0.125, 0.25, 0.5, 1.0, 2.0)
_ROW_LOOK = (0.1, 0.5, 0.9, 0.99, 1.0)

#: The rate of each round of the expert bias's rule that set-up runs before
#: anything is checked or timed (the configuration file's ``departures``
#: says why; ``train_steps_trinity``'s schedule): a forward pass over every
#: token batch a round.
BALANCE_RATES = (0.02,) * 4 + (0.01,) * 4 + (0.005,) * 4


def layer_mixers(config: dict) -> list:
    """``flops_solar2.layer_mixers`` (``"gqa"`` or ``"kda"`` of each layer
    kept, from its PUBLISHED index), of a file whose ``layers_kept`` lists
    as many layers as it says it has."""
    if len(config["layers_kept"]) != config["num_hidden_layers"]:
        raise ValueError("layers_kept does not list num_hidden_layers layers")
    return flops_solar2.layer_mixers(config)


def program_config(config: dict):
    """The published keys as ``accl_tpu.models.TransformerConfig``."""
    import jax.numpy as jnp

    from accl_tpu.models import DeltaAttention, LayerKind, TransformerConfig

    program, assumed = config["program"], config["assumed"]
    la = config["linear_attn_config"]
    if config["model_type"] != "solar_open2":
        raise ValueError("the block is solar_open2's")
    if (
        config["use_rope"] or not config["use_gqa_gate"]
        or config["kda_use_full_proj"] or not config["kda_allow_neg_eigval"]
        or config["first_k_dense_replace"] or la["num_kv_heads"] is not None
        or la["num_heads"] != config["num_attention_heads"]
        or la["head_dim"] != config["head_dim"]
        or config["tie_word_embeddings"]
    ):
        raise ValueError(
            "the variant is the softmax layers without position and with "
            "their gate, KDA's gate projections through rank head_dim and "
            "its write strength in (0, 2), on the attention's heads at its "
            "head width, every layer an expert layer, the head untied"
        )
    layers = tuple(
        LayerKind(
            mixer="attention" if mixer == "gqa" else "kda", rope=False,
            ffn="moe", d_ff=config["moe_intermediate_size"],
        )
        for mixer in layer_mixers(config)
    )
    held, of = config["n_routed_experts"], config["num_router_experts"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        layers=layers,
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]
        ],
        # no layer rotates (use_rope false): "rope" only says that the tree
        # holds no position table
        pos_embedding="rope",
        rope_base=float(config["rope_theta"]),
        norm="rmsnorm",
        norm_eps=float(config["rms_norm_eps"]),
        ffn="swiglu",
        tie_head=config["tie_word_embeddings"],
        attn_gate=True,
        kda=DeltaAttention(
            head_dim=la["head_dim"],
            conv=la["short_conv_kernel_size"],
            lower_bound=None,              # the published gate: no bound
            beta_scale=2.0,                # kda_allow_neg_eigval
            gate_rank=la["head_dim"],      # kda_use_full_proj false
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
        * config["moe_intermediate_size"],
        moe_router_experts=None if of == held else of,
        moe_first_expert=config["first_expert"],
        moe_held_row_factor=float(program["held_row_factor"]),
        attention=program["attention"],
        remat=program["remat"],
    )


def reference_model(config: dict) -> dict:
    """The keyword arguments ``reference.layer`` takes, from the keys."""
    return dict(
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        top_k=config["num_experts_per_tok"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        first_expert=config["first_expert"],
    )


def reference_block(lp: dict) -> dict:
    """One layer of the program's parameter tree under the reference's
    names (the held experts' matrices stacked on a leading axis)."""
    moe = lp["moe"]
    out = {
        "input_layernorm": lp["ln1"],
        "post_attention_layernorm": lp["ln2"],
        "q_proj": lp["wq"], "k_proj": lp["wk"], "v_proj": lp["wv"],
        "o_proj": lp["wo"],
        "gate": moe["gate"], "expert_bias": moe["bias"],
        "experts.gate_proj": moe["w1"], "experts.up_proj": moe["w3"],
        "experts.down_proj": moe["w2"],
        "shared_experts.gate_proj": moe["shared"]["w1"],
        "shared_experts.up_proj": moe["shared"]["w3"],
        "shared_experts.down_proj": moe["shared"]["w2"],
    }
    if "a_log" not in lp:
        return dict(out, g_proj=lp["wg"])
    return dict(
        out,
        q_conv1d=lp["conv_q"], k_conv1d=lp["conv_k"], v_conv1d=lp["conv_v"],
        f_a_proj=lp["wf_a"], f_b_proj=lp["wf_b"],
        g_a_proj=lp["wg_a"], g_b_proj=lp["wg_b"],
        A_log=lp["a_log"], dt_bias=lp["dt_bias"], b_proj=lp["wbeta"],
        o_norm=lp["o_norm"],
    )


def reference_top(params: dict) -> dict:
    """The tree's leaves outside the layers under the reference's names."""
    return {
        "embed_tokens": params["embed"],
        "norm": params["ln_f"],
        "lm_head": params["head"],
    }


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the reference's names."""
    return dict(
        reference_top(params),
        layers=[reference_block(lp) for lp in params["layers"]],
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
        mixers = layer_mixers(self.config)
        self.mixers = {
            "kda_layers": mixers.count("kda"),
            "gqa_layers": mixers.count("gqa"),
            "expert_layers": len(mixers),
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
        # off the device while the reference works there: a layer of it
        # backwards in float32 beside 6.6 GB of weights would not fit
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
        before the first step, a layer at a time, forwards and then
        backwards: logits of the first sequence's positions ``rows``, the
        loss, each layer's routing facts, what each layer adds to the
        stream (the RMS of ``f`` over the RMS of ``h``, for the look at
        which scale keeps what alive), each KDA layer's log-decays'
        facts (``reference.gate_facts``), and the gradients, which
        ``_moved`` holds against the two steps' updates leaf by leaf, in
        the program's names.  ``fp8_weights``: the reference from weights
        rounded to e5m2; ``how``: keyword arguments that break a layer (the
        controls of ``perfbench/controls_solar2.py``), as is
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
            gate = None
            if "a_log" in lp:
                gate = reference.layer_gate_facts(
                    h, reference_block(rounded(lp)), n_head=model["n_head"]
                )
            return (
                new, rms(new - h) / rms(h),
                reference.routing_facts(picked, top_k), gate,
            )

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
                    {"embed_tokens": table["embed"]}, tokens
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
        facts, added, inputs, gates = [], [], [], []
        for lp in self.before["layers"]:
            inputs.append(h)
            h, layer_added, layer_facts, gate = one_layer(h, jax.device_put(lp))
            added.append(float(layer_added))
            facts.append(layer_facts)
            if gate is not None:
                gates.append({
                    k: np.asarray(v).tolist() for k, v in gate.items()
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
            d_h, d_lp = one_layer_back(inputs.pop(), lp, d_h)
            moved.update(self._moved(
                f"{i}.", lp, *stepped(lp, lambda tree: tree["layers"][i]), d_lp
            ))
            del d_lp                # a layer's float32 gradients: 3.1 GB
        d_top.update(embed_back({"embed": top["embed"]}, d_h))
        moved.update(self._moved(
            "", top, *stepped(top, lambda tree: {k: tree[k] for k in top}),
            d_top,
        ))
        self.blocks_added, self.gates = added, gates
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
        # histograms is a lower bound on such entries, a layer
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
            # the reference's RMS of what each layer adds over the RMS of
            # the stream it adds to, in the blocks' order
            "blocks_added": self.blocks_added,
            # the first batch's log-decays, a KDA layer each
            "kda_gates": self.gates,
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
        # over the KDA layers: (token, channel) log-decays under Ling-3.0's
        # bound of -5, and runs of 16 tokens whose sum passes -88, %
        mean = lambda key: float(100.0 * np.mean([g[key] for g in self.gates]))
        self.gate_share = {
            "under_bound": mean("under_bound"),
            "sub_blocks_past_float32": mean("sub_blocks_past_float32"),
            "chunks_remembered": mean("chunks_remembered"),
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
        if not self.gate_share["sub_blocks_past_float32"] > 0.0:
            bad.append(
                "no run of 16 tokens sums past -88 on any channel: the batch "
                f"does not work the unbounded gate's path ({self.gate_share})"
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
        result["facts"]["kda_gate_unbounded_share"] = self.gate_share
        if loops is not None:
            # the KDA scopes' instructions, loop bodies and all (what
            # ``kda_core_time_share`` and ``kda_proj_time_share`` read)
            result["facts"]["scope_ops_all"] = {
                s: names for s, names in loops.items()
                if s.startswith("accl.attn::kda")
            }
        return result
