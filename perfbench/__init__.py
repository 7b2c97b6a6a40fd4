"""The benchmark of ``BENCHMARK.json``: a harness driven by data.

Whatever belongs to one configuration, one traffic mix, one kind of
traffic or one per-layer metric sits in a file of its own, found by the
name in ``BENCHMARK.json``; a later PR adds files and manifest entries
and edits nothing that is here:

* a configuration        -> ``configs/<name>.json`` (the manifest gives the
  path), its plain reference beside it in ``reference/``;
* a traffic mix ``t``    -> ``workloads/t.json``: parameters only, with the
  name of its driver and a ``rehearsal`` block of tiny sizes;
* a kind of traffic ``d`` -> ``drivers/d.py``: ``Driver`` (see
  ``drivers/_base.py``), the one general generator its mixes share;
* a per-layer metric ``m`` -> ``layer_metrics/m.py``: ``read(ctx)``, which
  returns ``None`` where it finds nothing to read.

The yardstick itself: ``flops.py`` (operations and bytes from shapes),
``trace_reduce.py`` (profiler trace to busy, idle, kernel time, gaps),
``peaks.json`` (the chip's published peaks, with the source),
``manifest.py`` (the loader, which refuses what the driver refuses),
``tracing.py`` (profiler slices), ``run.py`` (the command).  Tests of it:
``python3 -m pytest perfbench/tests -q`` (CPU; not in tier-1).
"""
