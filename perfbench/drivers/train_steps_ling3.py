"""Traffic driver ``train_steps_ling3``: the closed loop of ``train_steps``
(steps back to back, one queued ahead, the window ends on the last loss)
over the Ling-3.0 hybrid block of ``accl_tpu.models`` as ONE chip of its
8-way expert-parallel group computes it: five KDA linear-attention layers
(a chunked gated delta rule with a decay a channel, ``accl_tpu/ops/kda.py``)
to one latent-attention layer (q straight from the hidden state, ONE shared
rope key head, a head-wise output gate), a leading dense layer, then expert
layers under a sigmoid router with a selection bias, group-limited top-8 of
512 (4 of 8 groups, a group's score the sum of its two best) with a shared
expert, one routing group of 64 experts held; through
``make_sharded_train_step`` on a world of one chip.

Set-up builds the program's config FIRST, so a tree whose
``TransformerConfig`` lacks the block fails at once.  The weights are the
seed's; the expert bias is then brought to balance by a fixed number of
rounds of its own rule (``balanced``).  Then the check, on the first batch,
against the plain float32 reference in ``perfbench/reference/
bailing_hybrid.py`` (KDA as the token-by-token recurrence; given the same
held range; a layer at a time, so that one layer's float32 weights are
alive at once):

* (a) the router's counters through ``make_sharded_router_probe``: tokens
  an expert a layer over all 512 and the entries held here against the
  reference's, both within the count of near-tie tokens; nothing dropped;
* (b) logits of the batch's first sequence through ``make_sharded_forward``,
  its LAST and its FIRST ``check_positions`` positions (late: 128 chunks of
  carried state; early: the convolutions' padding and ``S_0``): a row's
  relative error at its median, and all the rows' relative RMS and largest
  error (why not the rows clear of a near-tie, as the other held cells
  have it: the limits' comment below);
* (c) the loss the FIRST train step returns (through
  ``make_sharded_train_step`` itself) against the reference's of the batch.
"""

from __future__ import annotations

import math
import re

import numpy as np

from perfbench import flops_ling3
from perfbench.drivers import train_steps_trinity
from perfbench.drivers.train_steps_trinity import held_entries
from perfbench.reference import bailing_hybrid as reference

#: Limits of the check: the program (bf16 weights and activations, f32
#: accumulation, the KDA core's float32 products in one bf16 pass, f32
#: router sigmoid) against the float32 reference at "highest" matmul
#: precision.  Measured on the v5e at the published widths and seven layers
#: (my chip runs, PR 40: 20 runs at 20 seeds with a row's statistics, and 15
#: more at 15 seeds that read the all-row statistics, the loss and the
#: routing alike).
#:
#: ROUTING NEAR-TIES.  bf16 rounding of the hidden state moves a selection
#: score (``sigmoid + bias``, float32, a dot product over 2,560 columns)
#: by a fraction of a bf16 spacing of the scores' size, which can swap a
#: token's 8th and 9th expert among its kept groups, or its 4th and 5th
#: GROUP (and with the group all of its experts), where the float32
#: reference does not.  With 512 experts the scores are DENSE: 11-14% of a
#: layer's 16,384 tokens have one of the two gaps within a QUARTER of a
#: bf16 spacing (2^-8 of the layer's score RMS; ``reference.routing_facts``),
#: 38-44% within one, and 3.4-11% of a layer's tokens do swap an expert
#: (553-619 entries moved in the first expert layer, 1,607-1,770 in the
#: last).  A token is NEAR A TIE where either gap is within
#: NEAR_TIE_SPACINGS.  (a) Half the L1 distance between the program's
#: tokens-an-expert histogram over all 512 and the reference's must stay
#: under that layer's count of near-tie tokens TIMES the experts a token
#: has (a swapped group moves up to all eight of a token's entries): read
#: 553-1,770 against 14,280-17,816, largest ratio 0.11; and so must the
#: difference in the entries HELD here (read 0-181); no entry dropped.
#:
#: (b) LOGITS, ALL THE CHECKED ROWS.  The other held cells compare the rows
#: clear of a near-tie in every layer.  Here none is clear of its
#: NEIGHBOURS': a swapped token's hidden state enters the next three
#: tokens' q, k and v through the convolutions and the tokens after it
#: through the state, in each of the KDA layers that follow, so the error
#: spreads (the same weights cut to depth 1, no router, 2, 3, 4, 6 and 7,
#: my chip run, PR 40: the MEDIAN row 0.72, 0.96, 1.33, 1.9, 4.3, 4.7%, the
#: 99th centile 0.85, 11, 12, 15, 18, 19%; at depth 1 every row is within
#: 1.1%).  The rows two spacings clear of a tie in all six layers are 0-2 of
#: 1,024, and those half a spacing clear (200-223) read what all rows read
#: (8.3-9.8% against 9.6-10.5%).  So the check is on all 1,024 rows, by
#: statistics a cascade of swaps bounds and a wrong computation does not
#: pass: a row's relative error (L2 over the vocabulary) at its MEDIAN,
#: 6.39-8.71%; the rows' relative RMS, 9.30-11.02%; the largest error,
#: 1.14-1.73 on logits of RMS 1.04 (a row whose own expert swapped late).
#: Early and late rows read alike (medians 5.5-9.3% and 5.9-9.4%).
#: (c) The first step's loss against the reference's: 9.2e-7 to 1.82e-4
#: apart; in seven further runs on the final tree up to 2.30e-4 (and the
#: relative RMS down to 9.06%, everything else inside the ranges above):
#: over the 42 runs the readings' RMS is 0.95e-4, so the limit is 4.7 of it.
#:
#: CONTROLS, each at two seeds, each past at least one limit (my chip runs,
#: PR 40, ``.probe/ling3_controls.py``, not committed).  The nearest
#: precision below bf16, the reference from weights rounded to fp8 (e5m2,
#: ``lax.reduce_precision``): the median row 86.4-86.8%, relative RMS
#: 86.5-86.8%, largest error 4.68-4.77, loss 1.18e-3 to 1.39e-3 apart,
#: 30,688-43,974 entries a layer moved against 11,736-12,824 allowed: past
#: every limit, by 4.3, 3.5, 1.6, 2.6 and 2.5-3.5 times.  The reference with
#: the decay left out (the plain delta rule): 115-116%, 114%, 6.6-7.2,
#: 17,031-28,101 moved against 13,680-15,600 (its loss, 2.1e-4 to 2.5e-4
#: apart, passes: uniform ids at seeded weights hardly see the mixer).  The
#: reference with the convolutions left out: 109-110%, 109-110%, 6.5-6.6,
#: loss 6.0e-4 to 1.1e-3, 47,120-71,349 moved against 14,968-20,120.  Each
#: limit lies between its two readings: 2.3 times the largest median and
#: relative RMS read, and the largest error's and the loss's at the
#: geometric mean of the largest reading and e5m2's smallest (their two
#: readings are 2.7 and 6.5 times apart).
ROW_MEDIAN_LIMIT = 0.2
REL_RMS_LIMIT = 0.25
MAX_ABS_LIMIT = 2.9
LOSS_REL_LIMIT = 4.5e-4
NEAR_TIE_SPACINGS = 0.25
#: what a run prints beside what it is judged by, for the next look: the
#: count of near-tie tokens at other margins, a row's error at quantiles
_NEAR_TIE_LOOK = (0.125, 0.25, 0.5, 1.0, 2.0)
_ROW_LOOK = (0.1, 0.5, 0.9, 0.99, 1.0)

#: The rate of each round of the expert bias's rule that set-up runs before
#: anything is checked or timed (the configuration file's ``departures``
#: says why; ``train_steps_trinity``'s schedule): a forward pass over every
#: token batch a round.
BALANCE_RATES = (0.02,) * 4 + (0.01,) * 4 + (0.005,) * 4


def layer_kinds(config: dict):
    """``flops_ling3.layer_kinds`` (``(mixer, ffn)`` of each layer kept, from
    its PUBLISHED index), of a file whose ``layers_kept`` lists as many
    layers as it says it has."""
    if len(config["layers_kept"]) != config["num_hidden_layers"]:
        raise ValueError("layers_kept does not list num_hidden_layers layers")
    return flops_ling3.layer_kinds(config)


def program_config(config: dict):
    """The published keys as ``accl_tpu.models.TransformerConfig``."""
    import jax.numpy as jnp

    from accl_tpu.models import (
        DeltaAttention,
        LatentAttention,
        LayerKind,
        TransformerConfig,
    )

    program, assumed = config["program"], config["assumed"]
    if (config["model_type"], config["topk_method"], config["score_function"]) != (
        "bailing_hybrid", "noaux_tc", "sigmoid"
    ):
        raise ValueError("the block is bailing_hybrid's, noaux_tc over sigmoid")
    if (
        not config["kda_safe_gate"] or not config["no_kda_lora"]
        or config["use_kda_lora"] or not config["linear_silu"]
        or config["group_norm_size"] != 1
        or config["num_kv_heads_for_linear_attn"]
        or config["q_lora_rank"] is not None
        or config["rope_scaling"] is not None
        or config["gated_attention_proj_granularity_type"] != "head_wise"
        or config["use_bias"] or config["use_qkv_bias"]
        or config["num_nextn_predict_layers"]
        or not config["moe_router_enable_expert_bias"]
        or config["scale_router_input"] or config["hidden_act"] != "silu"
    ):
        raise ValueError(
            "the KDA variant is the safe gate at full rank with SiLU'd "
            "convolutions and a norm group a head, on the attention's heads; "
            "q has no latent, rope no scaling, the gate is head-wise, nothing "
            "has a bias, no prediction module, the router has its expert bias"
        )
    kept = config["layers_kept"]
    if any(
        config["expert_swiglu_limit_list"][i]
        or config["share_expert_swiglu_limit_list"][i] for i in kept
    ):
        raise ValueError("a kept layer has a SwiGLU clamp, which is not built")
    layers = tuple(
        LayerKind(
            mixer=mixer, rope=mixer == "latent", ffn=ffn,
            d_ff=config["intermediate_size"] if ffn == "dense"
            else config["moe_intermediate_size"],
        )
        for mixer, ffn in layer_kinds(config)
    )
    held, of = config["num_experts"], config["num_router_experts"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        layers=layers,
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]
        ],
        pos_embedding="rope",
        rope_base=float(config["rope_theta"]),
        norm="rmsnorm",
        norm_eps=float(config["rms_norm_eps"]),
        ffn="swiglu",
        tie_head=config["tie_word_embeddings"],
        attn_gate="head",
        latent=LatentAttention(
            q_rank=config["q_lora_rank"],
            kv_rank=config["kv_lora_rank"],
            nope_dim=config["qk_nope_head_dim"],
            rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"],
        ),
        kda=DeltaAttention(
            head_dim=config["head_dim"],
            conv=config["short_conv_kernel_size"],
            lower_bound=float(config["kda_lower_bound"]),
        ),
        n_experts=held,
        moe_top_k=config["num_experts_per_tok"],
        moe_capacity_factor=None,
        moe_norm_topk_prob=config["norm_topk_prob"],
        moe_aux_weight=0.0,
        moe_router_z_weight=0.0,
        moe_router=config["score_function"],
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_n_group=config["n_group"],
        moe_topk_group=config["topk_group"],
        moe_bias_rate=float(assumed["expert_bias_update"]),
        moe_shared_d_ff=config["num_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
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
        kda_lower_bound=float(config["kda_lower_bound"]),
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        rope_theta=float(config["rope_theta"]),
        top_k=config["num_experts_per_tok"],
        n_group=config["n_group"],
        topk_group=config["topk_group"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        first_expert=config["first_expert"],
    )


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under the reference's names (the held
    experts' matrices stacked on a leading axis)."""

    def layer(lp):
        out = {
            "input_layernorm": lp["ln1"],
            "post_attention_layernorm": lp["ln2"],
            "o_proj": lp["wo"], "g_proj": lp["wg"],
        }
        if "a_log" in lp:
            out.update({
                "q_proj": lp["wq"], "k_proj": lp["wk"], "v_proj": lp["wv"],
                "q_conv1d": lp["conv_q"], "k_conv1d": lp["conv_k"],
                "v_conv1d": lp["conv_v"], "f_proj": lp["wf"],
                "A_log": lp["a_log"], "dt_bias": lp["dt_bias"],
                "b_proj": lp["wbeta"], "o_norm": lp["o_norm"],
            })
        else:
            out.update({
                "q_proj": lp["wq"], "kv_a_proj_with_mqa": lp["wkv_a"],
                "kv_a_layernorm": lp["kv_a_norm"], "kv_b_proj": lp["wkv_b"],
            })
        if "moe" not in lp:
            out.update({
                "mlp.gate_proj": lp["w1"], "mlp.up_proj": lp["w3"],
                "mlp.down_proj": lp["w2"],
            })
            return out
        moe = lp["moe"]
        out.update({
            "gate": moe["gate"], "expert_bias": moe["bias"],
            "experts.gate_proj": moe["w1"], "experts.up_proj": moe["w3"],
            "experts.down_proj": moe["w2"],
            "shared_experts.gate_proj": moe["shared"]["w1"],
            "shared_experts.up_proj": moe["shared"]["w3"],
            "shared_experts.down_proj": moe["shared"]["w2"],
        })
        return out

    return {
        "embed_tokens": params["embed"],
        "norm": params["ln_f"],
        "lm_head": params["head"],
        "layers": [layer(lp) for lp in params["layers"]],
    }


def balanced(probe, params, batches, put, rates=BALANCE_RATES):
    """``params`` with every expert layer's ``bias`` moved by ``len(rates)``
    rounds of the model's own rule (``reference.moved_bias``), each from the
    tokens an expert that the program's router probe counts over all of
    ``batches``; ``put`` places a bias on the device as the tree has it."""
    moe = [i for i, lp in enumerate(params["layers"]) if "moe" in lp]
    for rate in rates:
        counts = sum(
            np.asarray(probe(params, b)["expert_tokens"], np.int64)
            for b in batches
        )
        for row, i in zip(counts, moe):
            bank = params["layers"][i]["moe"]
            bank["bias"] = put(reference.moved_bias(bank["bias"], row, rate))
    return params


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>\S+) \(.*\{\s*$")
_CONTROL_FLOW = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}"
)


def scoped_instructions(hlo_text: str) -> dict:
    """``{scope: [instruction names]}`` of a compiled module's text over
    the entry computation AND the bodies and conditions of its loops and
    branches, nested ones too: the computations whose instructions run as
    device events of their own (``scope_ops.scopes_of`` reads the entry
    alone, which is all the other cells' steps have; the KDA core's scan
    over the chunks is a loop).  What a fusion or a reduction calls runs
    inside its caller's event and is not walked."""
    from perfbench import scope_ops

    computations, name, entry = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m is not None:
            name = m["name"]
            computations[name] = []
            if line.startswith("ENTRY "):
                entry = name
        elif name is not None:
            computations[name].append(line)
    out: dict = {}
    todo, seen = [entry], {entry}
    while todo:
        for line in computations.get(todo.pop(), ()):
            for one, many in _CONTROL_FLOW.findall(line):
                for called in [one] + re.findall(r"%([\w.\-]+)", many):
                    if called and called not in seen:
                        seen.add(called)
                        todo.append(called)
            m = scope_ops._INSTRUCTION.match(line)
            if m is None:
                continue
            found = scope_ops.SCOPE.findall(m["op"])
            if found:
                out.setdefault(found[-1], []).append(m["name"])
    return out


class Driver(train_steps_trinity.Driver):
    """``_segment`` and ``_note_loss`` are ``train_steps``'; ``measure``
    adds the mixers' facts to ``train_steps_trinity``'s."""

    def setup(self) -> None:
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
        from accl_tpu.ops.kda import CHUNK

        self._mark("imports")
        tr = self.traffic
        B, T = int(tr["batch"]), int(tr["seq"])
        if T > cfg.max_seq:
            raise ValueError(
                f"seq {T} past max_position_embeddings {cfg.max_seq}"
            )
        self.B, self.T = B, T
        mesh = Mesh(np.array([self.device]).reshape(1, 1), ("dp", "tp"))
        mixers = [cfg.mixer(kind) for kind in cfg.layers]
        self.mixers = {
            "kda_layers": mixers.count("kda"),
            "mla_layers": mixers.count("latent"),
            "kda_chunk": CHUNK,
        }

        # the latent layer's core, as the program asks: on the width of
        # q's first part (``_attention``)
        q = jax.ShapeDtypeStruct(
            (B, cfg.n_heads, T, cfg.latent.nope_dim), jnp.dtype(cfg.dtype)
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
        params = balanced(
            probe, params, self.tokens,
            lambda bias: jax.device_put(
                bias, shardings["layers"][-1]["moe"]["bias"]
            ),
        )
        self._mark("bias_balanced")
        want_loss = self._check(fwd, probe, params, cfg)
        self._mark("reference_check")

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
        self.params = params
        for i in range(2):
            self.params, loss = self.step(
                self.params, self.tokens[i % n], self.targets[i % n]
            )
            if i == 0:
                self._check_loss(float(loss), want_loss)
        self._note_loss(float(loss))
        self._mark("step_warm_up")

    # -- the check -----------------------------------------------------------

    def _reference(self, params, tokens, targets, cfg, rows, how=None):
        """The reference on one batch, a layer at a time: logits of the
        first sequence's positions ``rows``, the loss, and each expert
        layer's routing facts.  ``how``: keyword arguments that break a
        layer (the controls)."""
        import jax

        model = reference_model(self.config)
        top_k, groups, kept = cfg.moe_top_k, cfg.moe_n_group, cfg.moe_topk_group
        weights = reference_weights(params)

        @jax.jit
        def one_layer(h, lp):
            with jax.default_matmul_precision("highest"):
                h, picked = reference.layer(
                    h, lp, q_block=min(512, self.T), **model, **(how or {})
                )
            if picked is None:
                return h, None
            return h, reference.routing_facts(picked, top_k, groups, kept)

        @jax.jit
        def finish(h, weights, targets):
            with jax.default_matmul_precision("highest"):
                want = reference.head(weights, h[0][rows])
                loss = reference.nll_from_hidden(weights, h, targets)
            return want, loss

        h = jax.jit(reference.embed)(weights, tokens)
        facts = []
        for lp in weights["layers"]:
            h, layer_facts = one_layer(h, lp)
            if layer_facts is not None:
                facts.append(layer_facts)
        top = {k: v for k, v in weights.items() if k != "layers"}
        want, loss = finish(h, top, targets)
        return want, float(loss), facts

    def _checked_rows(self):
        """The first sequence's positions whose logits are compared: its
        first ``check_positions`` and its last (all of it where those
        overlap)."""
        span = min(int(self.traffic["check_positions"]), self.T // 2)
        return np.concatenate(
            [np.arange(span), np.arange(self.T - span, self.T)]
        ), span

    def _check(self, fwd, probe, params, cfg) -> float:
        """Logits and router counters of the first batch against the
        reference; returns the reference's loss of that batch."""
        import jax
        import jax.numpy as jnp

        rows, span = self._checked_rows()
        tokens, targets = self.tokens[0], self.targets[0]
        got = jax.jit(lambda z: z[0][rows].astype(jnp.float32))(
            fwd(params, tokens)
        )
        counters = probe(params, tokens)
        first, held = cfg.moe_first_expert, cfg.n_experts
        want, want_loss, facts = self._reference(
            params, tokens, targets, cfg, rows
        )
        want_counts = np.stack([np.asarray(f[0]) for f in facts])
        want_hits = np.stack([np.asarray(f[1]) for f in facts])
        gaps = np.stack([np.asarray(f[2]) for f in facts])          # (L, N)
        # a swapped group moves up to all of a token's entries
        allowed = (gaps < NEAR_TIE_SPACINGS).sum(axis=1) * cfg.moe_top_k
        near = np.stack([(gaps < m).sum(axis=1) for m in _NEAR_TIE_LOOK])

        @jax.jit
        def compare(got, want):
            err, ref = got - want, jnp.sum(want ** 2, axis=1)
            return (
                jnp.sqrt(jnp.sum(err ** 2, axis=1) / ref),       # a row's
                jnp.sqrt(jnp.sum(err ** 2) / jnp.sum(ref)),
                jnp.max(jnp.abs(err)),
                jnp.sqrt(jnp.mean(want ** 2)),
            )

        by_row, rel_rms, max_abs, ref_rms = (
            np.asarray(x) for x in compare(got, want)
        )
        of = lambda x: [float(v) for v in np.quantile(x, _ROW_LOOK)]
        row_median = float(np.median(by_row))
        counts = np.asarray(counters["expert_tokens"])
        dropped = int(np.asarray(counters["dropped"]).sum())
        here = np.asarray(counters["held_entries"])
        hits = np.asarray(counters["group_tokens"])
        want_here = held_entries(want_counts, first, held)
        # an entry that went to another expert than the reference's moves
        # one count down and one up: half the L1 distance of the two
        # histograms is a lower bound on such entries, a layer
        moved = np.abs(counts - want_counts).sum(axis=1) // 2
        held_off = np.abs(here - want_here)
        entries = counts.sum(axis=1)
        self.attempted += 1
        self.check = {
            "positions": len(rows),
            "rel_rms": float(rel_rms), "max_abs": float(max_abs),
            "row_median": row_median,
            # a row's relative error at _ROW_LOOK's quantiles: all the
            # checked rows, the early ones, the late ones
            "row_look": of(by_row),
            "early_late": {"early": of(by_row[:span]), "late": of(by_row[span:])},
            "reference_rms": float(ref_rms), "attention": self.attention,
            "dropped": dropped,
            "moved_entries": moved.tolist(),
            "held_entries": here.tolist(),
            "reference_held_entries": want_here.tolist(),
            "allowed_entries": allowed.tolist(),
            "group_tokens": hits.tolist(),
            "reference_group_tokens": want_hits.tolist(),
            "near_ties": {
                str(m): near[i].tolist() for i, m in enumerate(_NEAR_TIE_LOOK)
            },
        }
        held_counts = counts[:, first:first + held]
        group = first // (cfg.router_experts() // cfg.moe_n_group)
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
            "group_hit_share": float(
                100.0 * hits[:, group].sum() / (hits.shape[0] * self.B * self.T)
            ),
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
        if (moved > allowed).any():
            bad.append(
                f"tokens an expert: {moved.tolist()} entries a layer moved "
                f"against the reference's routing, more than its near-ties "
                f"allow ({allowed.tolist()})"
            )
        if (held_off > allowed).any():
            bad.append(
                f"entries held here {here.tolist()} against the reference's "
                f"{want_here.tolist()}: further apart than its near-ties "
                f"allow ({allowed.tolist()})"
            )
        if bad:
            self.failed += 1
            self.problems.extend(bad)
        return want_loss

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
            # the scopes whose time is inside a loop, bodies and all
            result["facts"]["scope_ops_all"] = {
                s: names for s, names in loops.items()
                if s.startswith("accl.attn::kda")
            }
        return result
