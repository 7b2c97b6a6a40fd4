"""Traffic driver ``train_steps``: a closed loop of train steps through
``accl_tpu.models.make_sharded_train_step`` on a world of one chip.

Steps are dispatched back to back with one step queued ahead of the one
running; the window ends on ``block_until_ready`` of the last loss, and
tokens/s is the tokens of the steps completed over those seconds.

During set-up the program's own forward path (``make_sharded_forward``,
same attention lowering) is compared on one seeded sequence with the
plain float32 reference in ``perfbench/reference``.
"""

from __future__ import annotations

import math
import time

import numpy as np

from perfbench.drivers._base import DriverBase
from perfbench.reference import gpt_bigcode as reference

#: Tolerances of the logits check: the program (bf16 weights and
#: activations, f32 accumulation) against the float32 reference at
#: "highest" matmul precision, over the last 256 positions x 49,152 logits
#: whose own RMS is 1.28.  bf16 keeps 8 significant bits (unit roundoff
#: 2^-9); through six layers, the final norm and the tied head that adds
#: up to a relative RMS error of 1.54-1.67% (my chip runs, PR 22, 26 runs of
#: both cells), with a largest single error of 0.113-0.144, six to seven
#: standard deviations of the error, as the largest of 12.6 M samples is.
#: The limits are about 2.5x what was measured.  An fp8 shortcut (3-4
#: significant bits, unit roundoff 2^-4..2^-5, 16-32x bf16's) lands far
#: past both; so does a wrong mask, a dropped layer, a missing scale or a
#: head that is not tied.  f16 is no shortcut on this chip (Mosaic has no
#: f16), and it would be finer than bf16, not coarser.
REL_RMS_LIMIT = 0.04
MAX_ABS_LIMIT = 0.35


def program_config(config: dict):
    """The published keys as ``accl_tpu.models.TransformerConfig``."""
    import jax.numpy as jnp

    from accl_tpu.models import TransformerConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]
    ]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["n_embd"],
        n_heads=config["n_head"],
        n_kv_heads=1 if config["multi_query"] else None,
        n_layers=config["n_layer"],
        d_ff=config["n_inner"],
        max_seq=config["n_positions"],
        dtype=dtype,
        pos_embedding=config["program"]["pos_embedding"],
        attention=config["program"]["attention"],
        remat=config["program"]["remat"],
    )


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under GPT-BigCode's published names
    (``c_attn`` is q, k, v side by side)."""
    import jax.numpy as jnp

    return {
        "wte": params["embed"],
        "wpe": params["pos"],
        "ln_f_w": params["ln_f"],
        "layers": [
            {
                "ln_1_w": lp["ln1"],
                "c_attn_w": jnp.concatenate(
                    [lp["wq"], lp["wk"], lp["wv"]], axis=1
                ),
                "attn_c_proj_w": lp["wo"],
                "ln_2_w": lp["ln2"],
                "c_fc_w": lp["w1"],
                "mlp_c_proj_w": lp["w2"],
            }
            for lp in params["layers"]
        ],
    }


class Driver(DriverBase):
    def __init__(self, cell: dict, seed: int, devices, rehearse: bool):
        super().__init__(cell, seed, devices, rehearse)
        self.device = list(devices)[0]
        self.check: dict = {}

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from accl_tpu.models import (
            init_params,
            make_sharded_forward,
            make_sharded_train_step,
        )
        from accl_tpu.models.transformer import (
            normalize_spec,
            param_specs,
            resolve_attention,
        )

        self._mark("imports")
        cfg = program_config(self.config)
        tr = self.traffic
        B, T = int(tr["batch"]), int(tr["seq"])
        if T > cfg.max_seq:
            raise ValueError(f"seq {T} past n_positions {cfg.max_seq}")
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

        # weights: on the device, in one jitted call, from the seed, in
        # the type they are trained in
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
        # one array a batch, so a step costs no indexing program
        self.tokens, self.targets = list(tok), list(tgt)

        jax.block_until_ready((params, tok))
        self._mark("weights_and_tokens")
        # correctness: one seeded sequence through the program's forward
        # path against the plain reference, on the last positions
        fwd, _ = make_sharded_forward(cfg, mesh)
        self._check_logits(fwd, params, cfg)
        self._mark("reference_check")

        # the step is compiled ONCE, ahead of time, and that executable is
        # what every step calls: nothing can compile inside the window
        # (an argument of another shape or placement raises instead)
        step, _ = make_sharded_train_step(cfg, mesh, lr=float(tr["lr"]))
        self.step = step.lower(params, self.tokens[0], self.targets[0]).compile()
        mem = self.step.memory_analysis()
        live = (self.device.memory_stats() or {}).get("bytes_in_use", 0)
        # the allocator's peak_bytes_in_use counts live arrays and not a
        # running program's scratch (my chip run, PR 22: 3.6 GB reported
        # under a step whose compile needs 10.9 GiB), so the peak of a
        # step is the arrays alive at its start, its outputs that alias
        # no argument, and its scratch
        self.step_peak_bytes = int(
            live + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
        ) if mem is not None else 0
        # warm-up: the step twice (the second call sees the step's own
        # output as its input, as every later call does)
        self.params = params
        for i in range(2):
            self.params, loss = self.step(
                self.params, self.tokens[i % n], self.targets[i % n]
            )
        self._note_loss(float(loss))
        self._mark("step_warm_up")

    def _check_logits(self, fwd, params, cfg) -> None:
        import jax
        import jax.numpy as jnp

        last = min(int(self.traffic["check_positions"]), self.T)
        seq = self.tokens[0][:1]                                # (1, T)
        got = jax.jit(lambda z: z[0, self.T - last:].astype(jnp.float32))(
            fwd(params, seq)
        )

        def ref(weights, tokens):
            with jax.default_matmul_precision("highest"):
                return reference.logits(
                    weights, tokens, n_head=cfg.n_heads, last=last,
                    q_block=min(512, self.T),
                )

        want = jax.jit(ref)(reference_weights(params), seq[0])

        def compare(got, want):
            err = got - want
            return (
                jnp.sqrt(jnp.mean(err ** 2) / jnp.mean(want ** 2)),
                jnp.max(jnp.abs(err)),
                jnp.sqrt(jnp.mean(want ** 2)),
            )

        rel_rms, max_abs, ref_rms = (float(x) for x in jax.jit(compare)(got, want))
        self.attempted += 1
        self.check = {
            "positions": last, "rel_rms": rel_rms, "max_abs": max_abs,
            "reference_rms": ref_rms, "attention": self.attention,
        }
        if not (rel_rms <= REL_RMS_LIMIT and max_abs <= MAX_ABS_LIMIT):
            self.failed += 1
            self.problems.append(
                f"logits differ from the reference: rel rms {rel_rms:.4g} "
                f"(limit {REL_RMS_LIMIT}), max abs {max_abs:.4g} "
                f"(limit {MAX_ABS_LIMIT})"
            )

    def _note_loss(self, loss: float) -> None:
        self.attempted += 1
        if not math.isfinite(loss):
            self.failed += 1
            self.problems.append(f"non-finite loss {loss}")

    # -- the timed window ------------------------------------------------------

    def _segment(self, until, max_steps, tracer) -> tuple:
        """Steps back to back, one queued ahead, until ``until`` (a
        perf_counter instant) or ``max_steps``; returns (steps, seconds),
        the seconds ending when the last loss is ready."""
        n = len(self.tokens)
        steps, pending = 0, None
        t0 = time.perf_counter()
        while True:
            i = self._i
            self._i += 1
            with tracer.span("bench::step"):
                self.params, loss = self.step(
                    self.params, self.tokens[i % n], self.targets[i % n]
                )
            steps += 1
            if pending is not None:
                with tracer.span("bench::wait"):
                    self._note_loss(float(pending))
            pending = loss
            if steps >= max_steps or time.perf_counter() >= until:
                break
        with tracer.span("bench::wait"):
            self._note_loss(float(pending))
        return steps, time.perf_counter() - t0

    def measure(self, seconds: float, tracer) -> dict:
        self._i = 0
        deadline = time.perf_counter() + seconds
        steps, active = 0, 0.0
        if tracer.enabled:
            # two steps, then a traced slice of a few, then the rest
            s, dt = self._segment(deadline, 2, tracer)
            steps, active = steps + s, active + dt
            before = tracer.overhead_s
            tracer.start("steps")
            s, dt = self._segment(
                math.inf, int(self.traffic["trace_steps"]), tracer
            )
            tracer.stop()
            deadline += tracer.overhead_s - before
            steps, active = steps + s, active + dt
            self.traced_steps = s
        if time.perf_counter() < deadline:
            s, dt = self._segment(deadline, math.inf, tracer)
            steps, active = steps + s, active + dt
        tokens_per_step = self.B * self.T
        rate = steps * tokens_per_step / active
        return {
            "memory_peak_bytes": self.step_peak_bytes,
            "metrics": {"train_tokens_per_s": rate},
            "facts": {
                "steps": steps, "active_s": active,
                "tokens_per_step": tokens_per_step,
                "tokens_per_s": rate,
                "seq": self.T, "batch": self.B,
                "traced_steps": getattr(self, "traced_steps", 0),
                "check": self.check,
                "samples": {"steps": steps},
            },
        }
