"""XLA profiler (xprof) hooks — the device-side tracing surface.

The reference's tracing story is a free-running hardware counter copied
into exchange memory per call (`ccl_offload_control.c:2279-2303`) plus
host timers; the TPU-native equivalents layer up:

* per-call ns: ``Request.get_duration_ns`` (already on every tier);
* per-call records: the telemetry plane (``accl_tpu.telemetry``) rings
  every completion into the flight recorder and exports Chrome/Perfetto
  spans named ``accl::<op>`` — the name the gang engine's own
  :func:`annotate` span of a call carries in the xprof timeline, so
  host ranges and exported spans line up;
* host spans: :func:`annotate` is the ONE place the package makes a
  ``jax.profiler.TraceAnnotation``; the table below lists every span;
* device spans: :func:`device_scope` names a region *inside* a jitted
  program (XLA op metadata), so kernels show up attributed in the trace
  viewer;
* whole-program capture: :func:`trace` / :func:`start_server` drive
  ``jax.profiler`` — open the result in xprof/tensorboard or perfetto.

Host spans, on the profiler's clock (the device trace's).  Names are
constants; what a reader needs beyond a duration rides as keyword stats
(``comm``).  There is no switch: a span costs a fraction of a
microsecond while no trace is being taken.

======================== ==================================================
``accl.facade::call``    ``core.py``, ``@annotated`` on a public collective:
                         entry to return, on the rank's calling thread
``accl.facade::prepare`` ``core.py``, the four swept collectives: the
                         public method up to ``_launch`` (counts, plan
                         lookup, hier/pipeline decisions, ``CallOptions``)
``accl.facade::plan``    ``core.py``, ``@annotated`` on ``_plan_for``
``accl.facade::membership`` ``core.py`` ``_launch``: ``_membership_intake``
``accl.facade::arbiter`` ``core.py`` ``_launch``: ``_arbiter_gate``
``accl.facade::contract`` ``core.py`` ``_launch``: ``_contract_gate``
``accl.facade::meta``    ``core.py`` ``_launch``: compression counters +
                         ``_call_meta``
``accl.facade::submit``  ``core.py`` ``_launch``: ``engine.start`` +
                         ``tel.attach`` (holds ``accl::<op>`` on the rank
                         that completes the gang slot)
``accl.facade::wait``    ``core.py`` ``_launch``: ``req.wait`` through
                         ``_check_failed`` / ``_arbiter_done`` (sync calls)
``accl::<op>``           ``backends/xla/engine.py`` ``_execute_calls``: one
                         gang call on the thread that assembled its slot
``accl::fused<k>_decomposed`` the same, a fused call that missed the ring
``accl.gang::assemble``  ``engine.py`` ``_run_op_device[_prepared]``:
                         operand checks, assembled global, program lookup
``accl.gang::dispatch``  ``engine.py``: the one program call
``accl.gang::adopt``     ``engine.py``: ``_adopt_out_shards``
``accl.gang::park``      ``engine.py``: ``_park_inflight``
``accl::batch[n]``       ``engine.py`` ``_dispatch_batch_fused``: a fused
                         batch of n collectives
``accl.batch::flush``    ``core.py`` ``flush``: the whole of it, on each
                         rank's thread (``comm``; ``batch``: the handle's
                         batch counter, equal on every rank, so the rank
                         threads' spans of one window share it)
``accl.batch::submit``   ``core.py`` ``_dispatch_pending``:
                         ``engine.start_batch``, only where calls were
                         queued (``batch``, ``n`` calls); holds
                         ``accl.ring::batch`` on the rank that completes
                         the gang slot
``accl.batch::drain``    ``core.py`` ``flush``: ``engine.drain_inflight``
                         (``batch``); it waits only for what is LAUNCHED,
                         so on the ranks that flush before the last it
                         returns at once
``accl.ring::batch``     ``backends/xla/cmdring.py`` ``run_batch``: a
                         matched batch tried ring-resident, entry to
                         return (``comm``, ``n`` positions); ONE span a
                         stage a window below, never one a slot
``accl.ring::plan``      ``_plan_batch``: the breaker and tuning gates and
                         a plan a position
``accl.ring::deps``      ``_dispatch_window``:
                         ``_wait_written_dependencies``
``accl.ring::encode``    ``_dispatch_window``: the window's shape, then
                         under the ring's lock the slot rows, counters,
                         ``_WindowPark``, per-slot introspection and the
                         written-root ledger, then the chaos hook
                         (``window``: its id)
``accl.ring::assemble``  ``_launch_window``: the operand globals
``accl::cmdring[n]``     ``_launch_window``: ``ops/cmdring.py``
                         ``run_windows``, the window's ONE program call
                         with what it needs first; holds the next two
``accl.ring::slots``     ``run_windows``: the slot words' device array
                         from the ring's ``KeptSlots`` (PR 51): the words
                         made window-relative and looked up by mesh and
                         content; a warm window ends there (a hit), only
                         words never sent before are ``np.tile``d and
                         ``jax.device_put`` onto the mesh (a put)
``accl.ring::program``   ``run_windows``: the window program's ``lru_cache``
                         lookup and its one call
``accl.ring::adopt``     ``_launch_window``: ``_adopt_out_shards`` a slot
``accl.ring::park``      ``_dispatch_window``: ``_park_window``
                         (``window``)
``accl.window::ready``   ``overlap.py`` ``InflightWindow._complete``: the
                         drainer in the parked entry's waiter.  A blocking
                         gang call's is ``block_until_ready`` alone; a
                         window's holds the next three
``accl.ring::wait``      ``_park_window``'s waiter, on the drainer:
                         ``jax.block_until_ready`` on the status global
                         alone (``window``, as ``::encode`` and ``::park``
                         carry it: the drainer's spans of a window join
                         the launching thread's by id)
``accl.ring::status``    the same: ``status_view`` of the ONE status shard
                         whose copy to the host ``run_windows`` asked for
                         at launch (PR 51: ``np.asarray`` of a literal
                         that is there or on its way, no second round
                         trip), then the window's base put back on the
                         window-relative ``seqn`` column (``window``)
``accl.ring::settle``    the same: ``_settle_window`` under the ring's
                         lock, and the park's event (``window``)
``accl.window::complete`` ``overlap.py``: requests completed, telemetry
                         record, done callbacks
======================== ==================================================

Only the ``accl::`` names are read by the benchmark's ``engine_span_us``,
``facade_self_us`` and ``breakdown``; the stage spans are ``accl.<layer>::``
so that those keep reading what they read (``perfbench/stage_spans.py``
reads the blocking call's stages, ``perfbench/window_spans.py`` the
batched window's, ``perfbench/runtime_spans.py`` what the two opened
spans hold and the runtime's own events inside the dispatches).  A
blocking call outside a batch carries no ``accl.batch::`` or
``accl.ring::`` span.  The telemetry plane's window log (``cmdring.py``
``_log_window``, basis ``"host"``) is on the ``perf_counter`` clock and
is NOT what the benchmark reads.

Device scopes (:func:`device_scope`), inside the jitted train step and
forward.  The name lands in every covered instruction's ``op_name``
(backward ops as ``transpose(jvp(<scope>))``), which the COMPILED
program's text keeps; a v5e trace names its events by instruction and
drops ``op_name``, so a reader joins the two by instruction name
(``perfbench/scope_ops.py``).

======================== ==================================================
``accl.attn::core``      ``models/mixers/attention.py`` ``_attn_partial``:
                         the
                         attention call (flash kernels, or the XLA forms),
                         on the K/V heads the layer's kind has
                         (``LayerKind.kv_heads``); a head in two parts
                         where ``LayerKind.heads`` rotates only its first
                         columns (128 without position + a rotating 64 as
                         the scores' second part, v of its own width);
                         under ``remat`` ``flash_fwd`` runs ONCE a layer
                         (the block keeps its ``o`` and ``lse`` by name,
                         ``utils.remat.KEPT_UNDER_REMAT``: the same under
                         ``::window`` and ``::mla``)
``accl.attn::window``    the same under a ``LayerKind.window``: a sliding
                         layer's attention call; with the sink a query
                         head where the kind has one (``LayerKind.sink``:
                         the fold's first term, ``d sink`` a reduce of the
                         saved row statistics beside the backward kernel,
                         under this scope too)
``accl.attn::gqa_proj``  the same where the stack has KDA layers beside it
                         (a gated grouped-query layer without position
                         among linear-attention ones) or the layer a head
                         geometry (``LayerKind.heads``): q, k and v's
                         projections, the gate a channel, the heads'
                         transpose back and ``wo``; under a head geometry
                         ALSO what lies between the projections and the
                         core (the heads' reshape, the value scale, the
                         split into the two parts, their rope, the sink's
                         cast); beside KDA layers that stretch stays
                         outside any scope, as Solar's and Olmo's pinned
                         programs have it; under ``remat`` q, k and v's
                         products and that stretch run ONCE (the block
                         keeps q, k, v and the two rotated parts as the
                         core takes them), ``wo``'s and the gate's twice
``accl.attn::latent``    ``models/mixers/latent.py``
                         ``_latent_attn_partial`` (a latent mixer, MLA): the
                         five projections (four where q has no latent),
                         the latent norms, the rope, the head-wise gate
``accl.attn::mla``       the same: the score/softmax/value core (the flash
                         kernels with two widths and ONE rope key head)
``accl.attn::kda``       ``models/mixers/kda.py`` ``_kda_partial`` (a KDA
                         mixer, ``LayerKind.mixer``
                         ``"kda"``): the core, ``ops/kda.py``
                         ``kda_chunked`` from normalised q, k, v, the
                         log-decay and beta to ``o``, forward and backward:
                         at whole-lane heads the kernels ``kda_fwd`` (the
                         forward, and under ``remat`` the replayed one) and
                         ``kda_bwd`` of ``ops/pallas/kda.py``, the scan over
                         the chunks fused into them; at any other shape the
                         XLA form, whose scan is a loop of the compiled
                         step (the body's instructions are device events
                         of their own).  At a decay a head (Gated
                         DeltaNet: a log-decay of ONE column) the same
                         kernels on heads padded to whole lanes (keys of
                         96 to 128, values of 192 to 256) with the head's
                         decay on every channel; the pads, the broadcast
                         and the cut of ``o`` are XLA's, under this scope
``accl.attn::kda_proj``  the same: everything round the core.  The seven
                         projections and ``wo`` are XLA's matmuls and beta
                         its fusion; the three float32 chains between them
                         and the core (``ops/kda.py``) are, at heads of
                         whole lanes, the kernels of ``ops/pallas/
                         kda_mixer.py``, one pass over HBM each:
                         ``kda_in_fwd`` / ``kda_in_bwd`` (q, k, v: the
                         convolution, SiLU, the L2 norm, to head-major),
                         ``kda_decay_fwd`` / ``kda_decay_bwd`` (the
                         log-decay's gate) and ``kda_out_fwd`` /
                         ``kda_out_bwd`` (the output norm and gate, back
                         from head-major); under ``remat`` a layer runs
                         each forward kernel twice, but the five matmuls
                         whose bf16 products they read once: the block
                         keeps those by name (``utils.remat.
                         KEPT_UNDER_REMAT``).  At any other shape
                         XLA's fusions: at a decay a head all three
                         chains (heads of 96 and 192 are no whole lanes;
                         the gate's projection is ``wa``, 30 columns, and
                         the output gate a SiLU).  Float32 in either
                         lowering,
                         inside the kernels too (only the projections
                         and their cotangents have the matmuls' type)
``accl.attn::ssd``       ``models/mixers/mamba2.py`` ``_mamba2_partial`` (a
                         Mamba-2 mixer, ``LayerKind.
                         mixer`` ``"mamba2"``): the core, ``ops/ssd.py``
                         ``ssd_mixer`` from token-major x, B, C and dt to
                         y, forward and backward: where a group's heads
                         fill whole lane rows and the state is whole lanes
                         the kernels ``ssd_fwd`` (the forward, and under
                         ``remat`` the replayed one) and ``ssd_bwd`` of
                         ``ops/pallas/ssd.py``, the scan over the chunks
                         fused into them, beside XLA's ``cumsum`` of the
                         log-decay inside a chunk and the heads' scalars'
                         small transposes; at any other shape the XLA
                         form (``ssd_chunked``: a chunk's masked ``(C B^T)
                         x`` and a scan over the chunks' states, which is
                         a loop of the compiled step whose body's
                         instructions are device events of their own)
                         round its head-major transposes
``accl.attn::mamba_proj`` the same: everything round the core, the five
                         projections, the convolutions with their bias,
                         SiLU, softplus, the gate, the grouped norm, ``wo``
                         (the two float32 chains, ``ops/ssd.py``
                         ``conv_silu`` and ``gated_group_norm``, are the
                         kernels ``mamba_in_fwd`` / ``mamba_in_bwd`` and
                         ``mamba_out_fwd`` / ``mamba_out_bwd`` of
                         ``ops/pallas/mamba_mixer.py`` at whole blocks of
                         1,024 columns, XLA's fusions at any other width;
                         under ``remat`` the five projections are kept by
                         name and multiplied out once, the chains replay)
``accl.attn::blockdiff`` ``models/mixers/attention.py`` ``_attn_partial``
                         under ``TransformerConfig.
                         diffusion``: the attention call on ``[noisy ;
                         clean]`` under the block-diffusion layout (the
                         flash kernels by its tile lists, or the XLA forms
                         under the dense mask)
``accl.diffusion::noise`` ``diffusion_noise``: the ids' noising inside the
                         step (a level a block, a mask a position)
``accl.loss::diffusion`` ``_diffusion_loss``: the head and the
                         ``1 / t``-weighted loss of the noisy half
``accl.moe::route``      ``models/moe.py``, dropless path: router matmul,
                         float32 softmax, (group-limited) top-k, the
                         balance losses, the Switch term under held
                         experts (``switch_balance``)
``accl.moe::dispatch``   the same: sort of the routing entries by expert,
                         group sizes, gather of the rows; under held
                         experts the gather's cotangent is the
                         ``place_rows`` kernel (``ops/pallas/
                         place_rows.py``, a plain nested scope of that
                         name) or k gathers a token
                         (``moe._gathers_win``), and the kernel's run
                         starts are computed here, once a layer
``accl.moe::experts``    the same: the three grouped matmuls and the gate
                         product.  The grouped matmuls are the Pallas
                         kernels of ``ops/pallas/grouped_matmul.py``, each
                         form under a plain nested scope of its own:
                         ``gmm_fwd``, ``gmm_dlhs`` (the input's gradient),
                         ``gmm_drhs`` (the weights')
``accl.moe::combine``    the same: unsort, weight by the router
                         probability, sum a token's k results; under held
                         experts the weighted ``place_rows`` kernel or the
                         k gathers, by the same rule
``accl.moe::shared``     the same, where the layer's parameters hold a
                         ``shared`` expert: the dense FFN (gated SiLU, or
                         ``relu ** 2``) every token passes through, added
                         to the routed result
``accl.moe::latent``     the same, where they hold ``w_down`` and ``w_up``
                         (a latent expert bank): the token into the
                         experts' width before the dispatch, the tokens'
                         weighted sums out of it after the combine
``accl.embed::grad``     ``models/transformer.py`` ``_gathered_rows_bwd``,
                         backward only: the embedding lookup's cotangent
                         placed on the table, by one matmul against the
                         ids' one-hot or by XLA's scatter-add
                         (``_onehot_wins``), with what the compiler fuses
                         behind it (the table's SGD update)
======================== ==================================================

Counters of the same paths, from the program and not from a trace: the
train step under ``TransformerConfig.diffusion`` returns ``masked_tokens``
(the positions its noise masked) and, under held experts,
``held_entries`` a layer; ``ops.pallas.attention.flash_tile_classes``
counts the tile pairs the flash kernels visit by class from the shapes,
under ``block_diffusion`` too (80 at L = 4096, blocks of 4: 56 interior,
8 ``block``, 8 ``strict``, 8 ``lower``).

jax is imported LAZILY: the emulator/native tiers (and the telemetry
plane's exporters) run in jax-free processes, and pulling a device
runtime into them just to name a span would be a side effect a tracing
utility must not have.  :func:`annotate` never imports jax: where the
process has not imported it, it returns one shared no-op context.
"""

from __future__ import annotations

import contextlib
import functools
import sys
from typing import Iterator, Optional

_NO_SPAN = contextlib.nullcontext()
#: ``jax.profiler.TraceAnnotation`` once some module of the process has
#: imported jax (a cache of a lookup, never a switch)
_trace_annotation = None


def _jax():
    import jax

    return jax


def annotate(name: str, **stats):
    """Host-side named range on the profiler's clock: the
    ``jax.profiler.TraceAnnotation`` itself (``stats`` become the
    event's stats), or the shared no-op context in a process that has
    not imported jax."""
    global _trace_annotation
    if _trace_annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return _NO_SPAN
        _trace_annotation = profiler.TraceAnnotation
    return _trace_annotation(name, **stats)


def annotated(name: str):
    """Decorator form of :func:`annotate`: the whole call of the
    decorated function is one span named ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with annotate(name):
                return fn(*args, **kwargs)

        return spanned

    return wrap


def device_scope(name: str):
    """In-program named scope (XLA op metadata; device rows of the
    trace); no-op off-jax."""
    try:
        return _jax().named_scope(name)
    except Exception:
        return contextlib.nullcontext()


@contextlib.contextmanager
def trace(logdir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a profiler trace of everything inside the block into
    ``logdir`` (xprof format; load with tensorboard or xprof)."""
    jax = _jax()
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_server(port: int = 9012):
    """Live capture endpoint: run once, then point
    ``tensorboard --logdir`` profile capture (or xprof) at this port."""
    return _jax().profiler.start_server(port)


def device_memory_profile(backend: Optional[str] = None) -> bytes:
    """pprof-format snapshot of live device allocations (the memory side
    of the reference's exchange-memory/buffer dumps)."""
    return _jax().profiler.device_memory_profile(backend)
