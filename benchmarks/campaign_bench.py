"""Campaign orchestrator overhead benchmark.

Times a small two-backend campaign over ``polybench-2mm`` twice against
a fresh trace cache: the ``cold`` row is backend work + orchestration,
the ``warm`` row is pure orchestrator + cache + aggregation overhead
(zero backend runs — the incremental-rerun path the CI regression gate
tracks), and ``speedup`` is their ratio (higher is better).

The same campaign also runs through the process scheduler (lease-based
ledger + worker subprocesses), first, while this process has not loaded
jax (a worker may need the accelerator): ``process_cold`` carries
worker spawn + interpreter startup on top of the backend work,
``process_warm`` is the ledger-resume path (all jobs already done, no
workers spawned), and ``process_overhead`` is process_cold/cold — the
price of crash-safe distribution on a workload this small (large
campaigns amortize it; see docs/API.md's decision guide).
"""

from __future__ import annotations

import shutil
import tempfile
import time


def campaign_bench():
    from repro.launch.campaign import CampaignRunner

    rows = []
    # process workers first, while this process has not imported
    # jax: a worker may need the accelerator (one process per chip)
    print("\n=== campaign scheduler: process workers ===")
    store_dir = tempfile.mkdtemp(prefix="bench-campaign-proc-")
    try:
        def run_proc():
            t0 = time.monotonic()
            result = CampaignRunner(
                "polybench-2mm", ("systolic", "gpu"), jobs=2,
                cache_dir=store_dir, scheduler="process",
                params={"polybench-2mm": {"ni": 48, "nj": 40, "nk": 32,
                                          "nl": 56}},
                backend_cfg={"systolic": {"rows": 32, "cols": 32}},
            ).run()
            return result, (time.monotonic() - t0) * 1e6

        pcold_res, pcold_us = run_proc()
        pwarm_res, pwarm_us = run_proc()
        assert pcold_res.executed == 2 and pwarm_res.executed == 0
        assert pcold_res.metrics["worker_deaths"] == 0
        print(f"process cold {pcold_us / 1e3:8.1f} ms  "
              f"({pcold_res.executed} backend run(s), worker spawn + "
              f"ledger)")
        print(f"process warm {pwarm_us / 1e3:8.1f} ms  "
              f"({pwarm_res.cache_hits} ledger resume(s), no workers)")
        rows.append(f"campaign.process_cold,{pcold_us:.1f},"
                    f"executed={pcold_res.executed}")
        rows.append(f"campaign.process_warm,{pwarm_us:.1f},"
                    f"cache_hits={pwarm_res.cache_hits}")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    print("\n=== campaign orchestrator: cold vs warm trace cache ===")
    cache_dir = tempfile.mkdtemp(prefix="bench-campaign-")
    try:
        def run():
            t0 = time.monotonic()
            result = CampaignRunner(
                "polybench-2mm", ("systolic", "gpu"), jobs=2,
                cache_dir=cache_dir,
                params={"polybench-2mm": {"ni": 48, "nj": 40, "nk": 32,
                                          "nl": 56}},
                backend_cfg={"systolic": {"rows": 32, "cols": 32}},
            ).run()
            return result, (time.monotonic() - t0) * 1e6

        cold_res, cold_us = run()
        warm_res, warm_us = run()
        assert cold_res.executed == 2 and warm_res.executed == 0
        speedup = cold_us / max(warm_us, 1.0)
        print(f"cold {cold_us / 1e3:8.1f} ms  ({cold_res.executed} "
              f"backend run(s))")
        print(f"warm {warm_us / 1e3:8.1f} ms  ({warm_res.cache_hits} "
              f"cache hit(s))  {speedup:.1f}x")
        rows.append(f"campaign.cold,{cold_us:.1f},"
                    f"executed={cold_res.executed}")
        rows.append(f"campaign.warm,{warm_us:.1f},"
                    f"cache_hits={warm_res.cache_hits}")
        rows.append(f"campaign.speedup,{speedup:.2f},cold/warm")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    overhead = pcold_us / max(cold_us, 1.0)
    print(f"process cold / thread cold: {overhead:.1f}x")
    rows.append(f"campaign.process_overhead,{overhead:.2f},"
                f"process_cold/thread_cold")
    return rows
