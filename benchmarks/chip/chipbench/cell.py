"""One run of one cell: set-up, the measured window, metrics, check.

Set-up runs the program once on a warm-up request (index -1) of the
same shapes as every timed request, so that nothing compiles in the
window.  The window is closed-loop: requests run back to back until
``seconds`` have passed; it ends with the last request, so rates and
times per request cover all the work and all the time of the window.
With ``trace`` the window is recorded by the profiler and the run
reports the cell's per-layer metrics instead of its end-to-end ones.
After the window the peak device memory is read, the program's state is
freed, and the sampled request is compared with the plain reference.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

from chipbench import devtrace, program, reference
from chipbench.manifest import load_module, peaks


@dataclasses.dataclass
class Context:
    """What a metric's reader may read (``metrics/<name>.py``)."""
    cell: object
    setup_s: float
    window_s: float
    n_done: int             # requests completed in the window
    units: int              # answers or compositions completed
    spans: list             # (name, request, start_s, end_s) in the window
    work: dict | None       # shapes of one request (program.work_of)
    summary: object         # devtrace.Summary of a traced run, else None
    peaks: dict             # published peaks of the chip

    def span_per_request(self, name):
        """Mean seconds per completed request spent in span ``name``."""
        total = sum(t1 - t0 for n, _, t0, t1 in self.spans if n == name)
        return total / self.n_done if self.n_done and total else None


class CompileCounter:
    """Backend compiles (not persistent-cache loads) and their seconds."""

    def __init__(self):
        from jax import monitoring
        self.n, self.seconds = 0, 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def _cache_counters():
    from repro.runtime.compile_cache import counters
    c = counters()
    return f"hits {c['persistent_cache_hits']} misses " \
        f"{c['persistent_cache_misses']}"


def chip_devices(chips):
    """The chips the cell asks for; no TPU, or too few, ends the run."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"needs {chips} TPU chip(s), but jax found {len(devs)} "
            f"{devs[0].platform!r} device(s) ({devs[0].device_kind})")
    return devs[:chips]


def memory_peak(devs):
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(cell, seed, seconds, trace, *, t_start=None, log=sys.stderr):
    """Run ``cell`` once and return the result object."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    devs = chip_devices(cell.chips)
    compiles = CompileCounter()
    spans = program.Spans(annotate=bool(trace))
    driver = program.load_driver(cell, seed, spans)
    with spans("setup"):
        driver.setup()
    setup_compiles = (compiles.n, compiles.seconds)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    attempted = failed = done = units = 0
    first = len(spans.rows)
    with spans("window"):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        while True:
            spans.request = attempted
            attempted += 1
            try:
                units += driver.request(attempted - 1)
                done += 1
            except Exception:                    # noqa: BLE001
                failed += 1
                traceback.print_exc(file=log)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        spans.request = None
    in_window = compiles.n - setup_compiles[0]
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = devtrace.Summary.from_profile_dir(trace_dir, spans.names)
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = memory_peak(devs)
    rec = driver.after_window() if done else None
    work = driver.work
    driver = None
    gc.collect()

    kind = devs[0].device_kind
    ctx = Context(cell=cell, setup_s=setup_s, window_s=window_s,
                  n_done=done, units=units,
                  spans=[r for r in spans.rows[first:]
                         if r[1] is not None],
                  work=work, summary=summary,
                  peaks=peaks(kind))
    log_requests(ctx.spans, log)
    print(f"setup_s={setup_s:.3f} window_s={window_s:.3f} done={done} "
          f"compiles: setup {setup_compiles[0]} ({setup_compiles[1]:.1f} s), "
          f"window {in_window}; persistent cache {_cache_counters()}",
          file=log, flush=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        got = load_module("metrics", m["name"]).read(ctx)
        if got is None:
            continue
        value, extra = got if isinstance(got, tuple) else (got, {})
        metrics[m["name"]] = {"value": value, "unit": m["unit"], **extra}

    limits = reference.limits()
    nums = dict.fromkeys(limits)
    if rec is not None:
        nums, _ = reference.numbers(cell, seed, rec)
    correct = failed == 0 and rec is not None and all(
        nums[k] <= limits[k] for k in limits)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s(), window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.idle_gaps()}
    result["setup"] = {"compiles": setup_compiles[0],
                       "compile_s": setup_compiles[1],
                       "compiles_in_window": in_window,
                       "sampled_request": rec["index"] if rec else None}
    result["checks"] = {k: {"value": nums[k], "limit": limits[k]}
                        for k in limits}
    return result


def log_requests(spans, log):
    """One stderr line per timed request: seconds in each span."""
    per = {}
    for name, req, t0, t1 in spans:
        per.setdefault(req, {}).setdefault(name, 0.0)
        per[req][name] += t1 - t0
    for req, d in sorted(per.items()):
        print(f"request {req}: " + " ".join(
            f"{k}={v:.3f}" for k, v in sorted(d.items())), file=log)


def report(result, out=sys.stdout, log=sys.stderr):
    """The result: compared numbers as the last lines of standard error,
    the result object as the last line of standard output."""
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=log)
    log.flush()
    print(json.dumps(result), file=out, flush=True)


def main(args, t_start):
    from chipbench.manifest import ROOT, resolve
    # the compile cache lives at a fixed path inside the checkout, so the
    # runs of one checkout share it and two checkouts share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax-cache")
    from repro.runtime import compile_cache
    compile_cache.configure()
    cell = resolve(args.workload)
    result = run(cell, args.seed, args.seconds, args.trace, t_start=t_start)
    report(result)
    return 0
