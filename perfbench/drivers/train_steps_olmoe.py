"""Traffic driver ``train_steps_olmoe``: the closed loop of
``train_steps`` (steps back to back, one queued ahead, the window ends on
the last loss) over the OLMoE block of ``accl_tpu.models``: RMSNorm,
QK-norm, RoPE, dropless top-k experts, untied head, through
``make_sharded_train_step`` on a world of one chip.

Set-up builds the program's config FIRST, so a tree whose
``TransformerConfig`` lacks the block fails at once.  Then the check, on
the first batch and the seeded weights, against the plain float32
reference in ``perfbench/reference/olmoe.py``:

* logits of the batch's first sequence through ``make_sharded_forward``,
  last ``check_positions`` positions;
* the loss the FIRST train step returns (cross entropy plus both router
  terms, through ``make_sharded_train_step`` itself) against the
  reference's loss of the same batch;
* the router's counters through ``make_sharded_router_probe``: no entry
  dropped, and tokens an expert a layer against the reference's top-k.
"""

from __future__ import annotations

import math

import numpy as np

from perfbench import scope_ops
from perfbench.drivers import train_steps
from perfbench.reference import olmoe as reference

#: Limits of the check: the program (bf16 weights and activations, f32
#: accumulation, f32 router softmax) against the float32 reference at
#: "highest" matmul precision.  Measured on the v5e at the published widths
#: and six layers (my chip runs, PR 26, 13 seeds), each limit about 2.5x the
#: largest reading.
#:
#: ROUTING NEAR-TIES.  bf16 rounding of the hidden state moves a router
#: logit by about a bf16 spacing of the logits' size, which can swap a
#: token's 8th and 9th expert; the float32 reference does not.  With seeded
#: weights one swapped expert changes that token's logits by more than all
#: of bf16's rounding (0.84-1.08% relative RMS on the positions without a
#: near-tie, 1.26-1.57% on all 256).  So: (a) routing itself is checked on
#: counts.  Half the L1 distance between the program's tokens-an-expert
#: histogram (its router probe) and the reference's bounds the entries that
#: went to another expert from below; it must stay under the number of
#: tokens whose 8th and 9th REFERENCE logits are within NEAR_TIE_SPACINGS
#: bf16 spacings (2^-8) of the layer's logit RMS: read 60-356 entries a
#: layer against 568-1,071 such tokens, largest ratio 0.37 (a top-7, a
#: renormalised or a bf16-softmax router moves thousands).  (b) Logits are
#: compared on the positions of the last ``check_positions`` that have no
#: such near-tie in ANY layer (118-156 of 256): relative RMS 0.0084-0.0108,
#: largest error 0.10-0.17 on logits of RMS 0.90.  (c) The first step's
#: loss against the reference's: 1e-6 to 4.5e-5 apart.
#:
#: The nearest precision below bf16: the same reference from weights
#: rounded to fp8 (e5m2, ``lax.reduce_precision``; a cast there and back is
#: elided by XLA) against itself reads relative RMS 0.408, largest error
#: 1.91, loss 1.4e-3 apart, 5,554-19,327 entries a layer moved: each of the
#: four is 13 to 20 times past its limit (e4m3 unscaled, whose range ends
#: above these weights: 0.967, 4.11, 2.9e-3).  So does a missing QK-norm, a
#: tied head, GELU for SiLU, seven experts or renormalised weights
#: (tests/test_olmoe.py breaks the reference each way).
REL_RMS_LIMIT = 0.027
MAX_ABS_LIMIT = 0.42
LOSS_REL_LIMIT = 1.1e-4
NEAR_TIE_SPACINGS = 2.0
#: what a run prints beside the one it is judged by, for the next look
_NEAR_TIE_LOOK = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
#: fewer positions without a near-tie than this: nothing to compare on
MIN_CLEAN_POSITIONS = 32


def program_config(config: dict):
    """The published keys as ``accl_tpu.models.TransformerConfig``."""
    import jax.numpy as jnp

    from accl_tpu.models import TransformerConfig

    program, assumed = config["program"], config["assumed"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]
        ],
        pos_embedding=program["pos_embedding"],
        rope_base=float(config["rope_theta"]),
        norm=program["norm"],
        ffn=program["ffn"],
        qk_norm=program["qk_norm"],
        tie_head=config["tie_word_embeddings"],
        n_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_capacity_factor=program["moe_capacity_factor"],
        moe_norm_topk_prob=config["norm_topk_prob"],
        moe_aux_weight=assumed["router_aux_loss_coef"],
        moe_router_z_weight=assumed["router_z_loss_coef"],
        attention=program["attention"],
        remat=program["remat"],
    )


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under OLMoE's published names (the
    experts' matrices stacked on a leading axis)."""
    return {
        "embed_tokens": params["embed"],
        "norm": params["ln_f"],
        "lm_head": params["head"],
        "layers": [
            {
                "input_layernorm": lp["ln1"],
                "q_proj": lp["wq"], "k_proj": lp["wk"], "v_proj": lp["wv"],
                "q_norm": lp["q_norm"], "k_norm": lp["k_norm"],
                "o_proj": lp["wo"],
                "post_attention_layernorm": lp["ln2"],
                "gate": lp["moe"]["gate"],
                "experts.gate_proj": lp["moe"]["w1"],
                "experts.up_proj": lp["moe"]["w3"],
                "experts.down_proj": lp["moe"]["w2"],
            }
            for lp in params["layers"]
        ],
    }


def router_facts(router_logits, top_k: int):
    """From the reference's router logits of one layer (N, E): tokens an
    expert under its top k; a token's gap between its k-th and (k+1)-th
    logit in bf16 spacings (2^-8) of the layer's logit RMS."""
    import jax
    import jax.numpy as jnp

    E = router_logits.shape[-1]
    top, top_e = jax.lax.top_k(router_logits, top_k + 1)
    counts = jnp.sum(jax.nn.one_hot(top_e[:, :top_k], E, dtype=jnp.int32),
                     axis=(0, 1))
    spacing = 2.0 ** -8 * jnp.sqrt(jnp.mean(router_logits ** 2))
    return counts, (top[:, top_k - 1] - top[:, top_k]) / spacing


class Driver(train_steps.Driver):
    """``_segment``, ``measure`` and ``_note_loss`` are ``train_steps``'."""

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

        self._mark("imports")
        tr = self.traffic
        B, T = int(tr["batch"]), int(tr["seq"])
        if T > cfg.max_seq:
            raise ValueError(
                f"seq {T} past max_position_embeddings {cfg.max_seq}"
            )
        self.B, self.T = B, T
        mesh = Mesh(np.array([self.device]).reshape(1, 1), ("dp", "tp"))

        q = jax.ShapeDtypeStruct(
            (B, cfg.n_heads, T, cfg.d_model // cfg.n_heads),
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
            lambda k: init_params(k, cfg), out_shardings=shardings
        )(key)

        n = int(tr["token_batches"])

        def make_tokens(k):
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

    def _check(self, fwd, probe, params, cfg) -> float:
        """Logits and router counters of the first batch against the
        reference; returns the reference's loss of that batch."""
        import jax
        import jax.numpy as jnp

        last = min(int(self.traffic["check_positions"]), self.T)
        tokens, targets = self.tokens[0], self.targets[0]
        got = jax.jit(lambda z: z[0, self.T - last:].astype(jnp.float32))(
            fwd(params, tokens)
        )
        counters = probe(params, tokens)

        def ref(weights, tokens, targets):
            with jax.default_matmul_precision("highest"):
                h, router = reference.hidden(
                    weights, tokens, n_head=cfg.n_heads, top_k=cfg.moe_top_k,
                    norm_topk_prob=cfg.moe_norm_topk_prob,
                    q_block=min(512, self.T),
                )
                want = reference.head(weights, h[0, self.T - last:])
                loss = reference.loss_from_hidden(
                    weights, h, router, targets, cfg.moe_top_k
                )
            facts = [router_facts(r, cfg.moe_top_k) for r in router]
            gaps = jnp.stack([f[1] for f in facts])             # (L, N)
            near = gaps < NEAR_TIE_SPACINGS
            return (
                want, loss, jnp.stack([f[0] for f in facts]),
                near.sum(axis=1),
                jnp.stack([(gaps < m).sum(axis=1) for m in _NEAR_TIE_LOOK]),
                ~near.any(axis=0)[self.T - last:self.T],
            )

        want, want_loss, want_counts, allowed, near, clean = jax.jit(ref)(
            reference_weights(params), tokens, targets
        )

        def compare(got, want, rows):
            err = (got - want) * rows[:, None]
            ref = want * rows[:, None]
            return (
                jnp.sqrt(jnp.sum(err ** 2) / jnp.sum(ref ** 2)),
                jnp.max(jnp.abs(err)),
                jnp.sqrt(jnp.sum(ref ** 2) / (rows.sum() * want.shape[1])),
            )

        compare = jax.jit(compare)
        rel_rms, max_abs, ref_rms = (
            float(x) for x in compare(got, want, clean.astype(jnp.float32))
        )
        rel_rms_all, max_abs_all, _ = (
            float(x) for x in compare(got, want, jnp.ones(last, jnp.float32))
        )
        counts = np.asarray(counters["expert_tokens"])
        dropped = int(np.asarray(counters["dropped"]).sum())
        # an entry that went to another expert than the reference's moves
        # one count down and one up: half the L1 distance of the two
        # histograms is a lower bound on such entries, a layer
        moved = np.abs(counts - np.asarray(want_counts)).sum(axis=1) // 2
        allowed, near = np.asarray(allowed), np.asarray(near)
        n_clean = int(np.asarray(clean).sum())
        self.attempted += 1
        self.check = {
            "positions": last, "clean_positions": n_clean,
            "rel_rms": rel_rms, "max_abs": max_abs,
            "rel_rms_all": rel_rms_all, "max_abs_all": max_abs_all,
            "reference_rms": ref_rms, "attention": self.attention,
            "dropped": dropped,
            "moved_entries": moved.tolist(),
            "allowed_entries": allowed.tolist(),
            "near_ties": {
                str(m): near[i].tolist() for i, m in enumerate(_NEAR_TIE_LOOK)
            },
        }
        self.router = {
            "expert_tokens": counts.tolist(),
            "load_imbalance": float((counts.max(axis=1)
                                     / counts.mean(axis=1)).max()),
        }
        bad = []
        if n_clean < MIN_CLEAN_POSITIONS:
            bad.append(
                f"only {n_clean} of the last {last} positions have no "
                f"router near-tie in any layer: too few to compare logits on"
            )
        elif not (rel_rms <= REL_RMS_LIMIT and max_abs <= MAX_ABS_LIMIT):
            bad.append(
                f"logits differ from the reference: rel rms {rel_rms:.4g} "
                f"(limit {REL_RMS_LIMIT}), max abs {max_abs:.4g} "
                f"(limit {MAX_ABS_LIMIT}) over {n_clean} positions"
            )
        if dropped:
            bad.append(f"{dropped} routing entries dropped, dropless")
        if (moved > allowed).any():
            bad.append(
                f"tokens an expert: {moved.tolist()} entries a layer moved "
                f"against the reference's top-k, more than its near-ties "
                f"allow ({allowed.tolist()})"
            )
        if bad:
            self.failed += 1
            self.problems.extend(bad)
        return float(want_loss)

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
        scope_ops_ = None
        if tracer.enabled:
            # before the window opens: which instruction of the step sits
            # under which device_scope (perfbench/scope_ops.py says why
            # the trace alone cannot tell)
            scope_ops_ = scope_ops.scopes_of(self.step.as_text())
        result = super().measure(seconds, tracer)
        result["facts"]["router"] = self.router
        if scope_ops_ is not None:
            result["facts"]["scope_ops"] = scope_ops_
        return result
