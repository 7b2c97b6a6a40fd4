"""Probes of what the installed jax and platform actually do.

The repository targets one installation — jax/jaxlib 0.9.0 — and holds
no branch for another.  What stays here are three facts that differ by
PLATFORM, not by version, and that test suites skip on loudly (a reason
string, never a silent numeric fudge):

* :func:`has_faithful_fp8_cast` — XLA's f32 -> fp8 cast rounds as
  ``ml_dtypes`` does on this backend;
* :func:`has_interpret_params` — the Pallas TPU interpreter runs here
  (the CPU tier's way of executing the Mosaic kernels);
* :func:`has_bitexact_replay` — a donated train-step output and a
  ``device_put`` clone replay to identical bits.

Importing this module does not import jax: the emulator/native tiers run
in processes that never load it.
"""

from __future__ import annotations

_fp8_cast_faithful: bool = None


def has_faithful_fp8_cast() -> bool:
    """True when XLA's f32 -> float8_e4m3fn cast rounds identically to
    ml_dtypes' numpy cast on this backend.  A backend may round a small
    fraction of values to the other neighboring representable, and then
    a device-tier compressed transfer cannot be checked bit-exactly
    against the ml_dtypes reference — scenario suites gate their fp8
    wire cases on this probe (a loud skip with a reason string, never a
    silent numeric fudge)."""
    global _fp8_cast_faithful
    if _fp8_cast_faithful is not None:
        return _fp8_cast_faithful
    import ml_dtypes
    import numpy as np

    import jax.numpy as jnp

    rng = np.random.default_rng(0xF8)
    x = (rng.standard_normal(4096) * 8.0).astype(np.float32)
    want = x.astype(ml_dtypes.float8_e4m3fn).view(np.uint8)
    got = np.asarray(
        jnp.asarray(x).astype(jnp.float8_e4m3fn)
    ).view(np.uint8)
    _fp8_cast_faithful = bool((want == got).all())
    return _fp8_cast_faithful


_interpret_probe = None


def _probe_interpret_params():
    """(ok, reason) for the Pallas TPU interpreter on this host: a
    trivial kernel must actually execute under it — some environments
    ship the name but fail at run time, so presence alone is not
    evidence."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    try:
        def k(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        out = pl.pallas_call(
            k,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=pltpu.InterpretParams(),
        )(jnp.zeros((8, 128), jnp.float32))
        out.block_until_ready()
    except Exception as e:
        return False, (
            "pltpu.InterpretParams probe failed: "
            f"{type(e).__name__}: {e}"
        )
    return True, ""


def has_interpret_params() -> bool:
    """True when ``pltpu.InterpretParams`` exists and a trivial kernel
    RUNS under it (probed once, cached).  The Pallas interpret-mode test
    suites gate on this so the long-standing environment failures skip
    loudly with :func:`interpret_params_reason` instead of sitting in
    the failure set — the loud-skip convention ``has_faithful_fp8_cast``
    established."""
    global _interpret_probe
    if _interpret_probe is None:
        _interpret_probe = _probe_interpret_params()
    return _interpret_probe[0]


def interpret_params_reason() -> str:
    """Why :func:`has_interpret_params` is False ('' when it is True) —
    the skip reason string the gated suites surface."""
    global _interpret_probe
    if _interpret_probe is None:
        _interpret_probe = _probe_interpret_params()
    return _interpret_probe[1]


_replay_probe = None


def _probe_bitexact_replay():
    """(ok, reason) for bit-exact train-step replay on this host: run a
    TINY sharded trainer step twice from value-identical states — once
    chained on the donated step OUTPUT, once from a ``device_put`` clone
    (exactly what a checkpoint restore produces).  Some XLA builds
    execute a provenance-dependent program (donated/aliased inputs pick
    different in-place kernels with a different FP reduction order), so
    a resumed run cannot be bit-comparable to an uninterrupted one even
    though save/restore and the data stream are value-faithful.  Each
    path is individually repeatable — this is replay instability, not
    nondeterminism, which is why it must be PROBED, not assumed."""
    try:
        import numpy as np

        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from .models import (
            TransformerConfig,
            init_params,
            make_sharded_train_step,
        )
    except Exception as e:  # pragma: no cover - broken env
        return False, f"replay probe unavailable: {type(e).__name__}: {e}"
    try:
        devs = jax.devices()
        tp = 2 if len(devs) >= 2 else 1
        dp = max(len(devs) // tp, 1)
        mesh = Mesh(np.array(devs[: dp * tp]).reshape(dp, tp), ("dp", "tp"))
        heads = max(2, tp)
        cfg = TransformerConfig(
            vocab=32, d_model=8 * heads, n_heads=heads, n_layers=1,
            d_ff=16 * heads, max_seq=8, dtype=jnp.float32,
        )
        step_fn, shard = make_sharded_train_step(cfg, mesh, lr=0.1)
        params = shard(init_params(jax.random.PRNGKey(0), cfg))
        rng = np.random.default_rng(7)
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab, (2 * dp, cfg.max_seq)), jnp.int32
        )
        tgts = jnp.asarray(
            rng.integers(0, cfg.vocab, (2 * dp, cfg.max_seq)), jnp.int32
        )
        p1, _ = step_fn(params, toks, tgts)
        clone = jax.tree.map(
            lambda a: jax.device_put(np.asarray(a).copy(), a.sharding), p1
        )
        _, chained = step_fn(p1, toks, tgts)
        _, replayed = step_fn(clone, toks, tgts)
        chained, replayed = float(chained), float(replayed)
    except Exception as e:
        return False, f"replay probe failed: {type(e).__name__}: {e}"
    if chained != replayed:
        return False, (
            "XLA executes a provenance-dependent program: the same step "
            "on value-identical params gives "
            f"{chained!r} chained vs {replayed!r} from a device_put "
            "clone — checkpoint resume cannot be bit-exact on this "
            "platform (restore IS a device_put)"
        )
    return True, ""


def has_bitexact_replay() -> bool:
    """True when a donated train-step output and a value-identical
    ``device_put`` clone replay to bit-identical results (probed once,
    cached).  Checkpoint-resume bit-exactness tests gate on this and
    skip LOUDLY with :func:`bitexact_replay_reason` where the platform
    cannot deliver it — the loud-skip convention of
    ``has_interpret_params``."""
    global _replay_probe
    if _replay_probe is None:
        _replay_probe = _probe_bitexact_replay()
    return _replay_probe[0]


def bitexact_replay_reason() -> str:
    """Why :func:`has_bitexact_replay` is False ('' when it is True)."""
    global _replay_probe
    if _replay_probe is None:
        _replay_probe = _probe_bitexact_replay()
    return _replay_probe[1]
