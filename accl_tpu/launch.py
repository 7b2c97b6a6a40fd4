"""Multi-process launcher: the ``mpirun + run.py`` role.

The reference runs N host processes under mpirun, each talking to its own
emulator process (``test/model/emulator/run.py``).  Here one command spawns
N Python processes, each running a user function as one rank of a socket-
fabric group:

    from accl_tpu.launch import launch_processes

    def main(accl, rank, world):
        ...

    launch_processes(main, world=4)

The user function runs in a fresh process with its ACCL handle constructed
from synthetic local addresses (ref generate_ranks' synthetic subnets).
"""

from __future__ import annotations

import glob
import multiprocessing as mp
import os
import pickle
import sys
import traceback
from typing import Callable, List, Optional


def _worker(fn_spec, rank, world, base_port, design_name, conn):
    try:
        # persistent XLA compilation cache, shared across rank processes
        # and across runs: the jax-backed dist tier compiles one program
        # per (op, wire-bucket, comm) and a cold cache pays that once per
        # PROCESS per RUN otherwise.  Only for jax-backed designs — the
        # emulator/socket/native tiers are numpy/C++ and keep their jax
        # import lazy (an unconditional import would tax every spawned
        # rank ~1 s for nothing).
        if design_name.startswith("xla"):
            from .utils.platform import use_compile_cache

            use_compile_cache()
        if isinstance(fn_spec, tuple):  # (script_path, fn_name) from the CLI
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "accl_user_script", fn_spec[0]
            )
            mod = importlib.util.module_from_spec(spec)
            sys.modules["accl_user_script"] = mod
            spec.loader.exec_module(mod)
            fn = getattr(mod, fn_spec[1])
        else:
            fn = pickle.loads(fn_spec)
        from .parallel.topology import Design, bootstrap

        accl = bootstrap(
            Design(design_name), world, rank=rank, base_port=base_port
        )
        try:
            result = fn(accl, rank, world)
        finally:
            accl.deinit()
        conn.send(("ok", result))
    except BaseException:
        conn.send(("error", traceback.format_exc()))


def _ranks_would_open_tpu() -> bool:
    """Whether a spawned jax rank process would initialize the TPU
    backend: ``JAX_PLATFORMS`` names it, or names nothing on a host
    that has the chips' device nodes.  Decided without importing jax —
    the launcher parent must never hold a chip itself."""
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if platforms:
        return "tpu" in platforms
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def launch_processes(
    fn: Callable,
    world: int,
    base_port: int = 47300,
    timeout: float = 120.0,
    design: str = "socket",
) -> List:
    """Run ``fn(accl, rank, world)`` in ``world`` separate OS processes over
    a per-rank TCP fabric; returns per-rank results, raises on any failure.

    ``design`` selects the engine tier: "socket" (Python emulator),
    "native_socket" (C++ engine) or "xla_dist" (one jax process a rank
    over ``jax.distributed`` — the CPU/gloo tier; refused on a TPU host,
    where each rank would open every chip).  ``fn`` is either a
    picklable module-level function or a ``(script_path, fn_name)`` tuple
    loaded fresh in each worker."""
    if design == "xla_dist" and _ranks_would_open_tpu():
        # every rank calls jax.distributed.initialize with no device
        # selection and opens EVERY local chip, and a chip belongs to one
        # process (chip run, PR 21: rank 1 dies at start-up with
        # "ABORTED: Internal error when accessing libtpu multi-process
        # lockfile", rank 0 then waits out the launcher's timeout)
        raise RuntimeError(
            "design='xla_dist' cannot run on a TPU host: each rank "
            "process would open every local chip, and a chip belongs to "
            "one process.  Drive all chips from ONE process "
            "(accl_tpu.core.xla_group), or hold the ranks to the CPU/gloo "
            "tier with JAX_PLATFORMS=cpu — the dist tier is exercised "
            "there only."
        )
    ctx = mp.get_context("spawn")
    payload = fn if isinstance(fn, tuple) else pickle.dumps(fn)
    procs = []
    conns = []
    for r in range(world):
        parent, child = ctx.Pipe()
        p = ctx.Process(
            target=_worker, args=(payload, r, world, base_port, design, child)
        )
        p.start()
        # drop the parent's copy of the child end so a crashed worker
        # surfaces as EOF instead of a silent full-timeout wait
        child.close()
        procs.append(p)
        conns.append(parent)
    results = [None] * world
    errors = []
    try:
        for r, (p, conn) in enumerate(zip(procs, conns)):
            try:
                if conn.poll(timeout):
                    status, value = conn.recv()
                    if status == "ok":
                        results[r] = value
                    else:
                        errors.append(f"rank {r}:\n{value}")
                else:
                    errors.append(f"rank {r}: no result within {timeout}s")
            except EOFError:
                # worker died before reporting (killed / OOM)
                errors.append(f"rank {r}: worker exited without a result")
    finally:
        # never leak rank processes, even when one died mid-collective and
        # the rest are blocked waiting for it; a rank stuck inside a C++
        # collective (gloo) can shrug off SIGTERM, so escalate to SIGKILL
        for p in procs:
            p.join(5)
            if p.is_alive():
                p.terminate()
                p.join(5)
            if p.is_alive():
                p.kill()
                p.join(5)
    if errors:
        raise RuntimeError("launch failed:\n" + "\n".join(errors))
    return results


def main(argv: Optional[List[str]] = None) -> int:
    """CLI: ``python -m accl_tpu.launch -n 4 script.py`` runs script.py's
    ``main(accl, rank, world)`` across 4 processes."""
    import argparse
    import importlib.util

    ap = argparse.ArgumentParser(description="accl_tpu multi-process launcher")
    ap.add_argument("-n", "--world", type=int, default=2)
    ap.add_argument("--base-port", type=int, default=47300)
    ap.add_argument(
        "--design",
        default="socket",
        choices=["socket", "native_socket"],
        help="per-rank engine tier: Python emulator or native C++ engine",
    )
    ap.add_argument("script")
    args = ap.parse_args(argv)

    launch_processes(
        (os.path.abspath(args.script), "main"),
        args.world,
        base_port=args.base_port,
        design=args.design,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
