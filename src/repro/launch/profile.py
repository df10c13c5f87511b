"""GainSight profiling driver: the paper's workflow as a framework feature.

A thin CLI over :class:`repro.core.ProfileSession` - for a given
*registered workload* (``repro.workloads``; ``--arch`` accepts any
registry name, not just the ten architecture configs), run the selected
registry backend, the analytical frontend, and the heterogeneous-memory
composition, and emit the report (JSON + console).

  PYTHONPATH=src python -m repro profile --arch tinyllama_1_1b \
      --backend systolic --dataflow ws --pe 128
  PYTHONPATH=src python -m repro profile --arch tinyllama_1_1b \
      --backend gpu --seq 128
  PYTHONPATH=src python -m repro profile --arch polybench-2mm \
      --backend systolic
  PYTHONPATH=src python -m repro profile --arch mamba2_130m \
      --backend tpu --seq 64
  PYTHONPATH=src python -m repro profile --backend systolic --dry-run

(``python -m repro.launch.profile ...`` still works; the legacy
``profile_systolic``/``profile_gpu``/``profile_tpu`` entry points remain
as shims over the session API, and the workload builders that used to be
hand-wired here live in ``repro.workloads.suites`` now.)
"""

from __future__ import annotations

import argparse
import json

from repro.backends.systolic import GemmLayer
from repro.core import ProfileSession
from repro.devices import get_device_family
from repro.runtime import compile_cache
from repro.workloads import (get_workload, transformer_gemms,  # noqa: F401
                             transformer_program, tpu_step_workload)

# The paper device set, resolved through the device-family registry
# (importing the DEFAULT_DEVICES / SI_GCRAM / HYBRID_GCRAM literals is
# deprecated for launchers; the family build is object-identical).
_SRAM_DEV, SI_GCRAM, HYBRID_GCRAM = get_device_family(
    "sram-gaincell-default").build()


def _op_program(cfg, seq):
    """Back-compat alias for :func:`repro.workloads.transformer_program`."""
    return transformer_program(cfg, seq)


def _tpu_workload(cfg, seq):
    """Back-compat alias for :func:`repro.workloads.tpu_step_workload`."""
    return tpu_step_workload(cfg, seq)


def _summarize(session: ProfileSession, out: str | None,
               csv_out: str | None = None) -> dict:
    """Console summary + composition entries + optional JSON/CSV dump."""
    report = session.report()
    print(json.dumps(
        {k: {kk: vv for kk, vv in v.items() if kk != "devices"}
         for k, v in report["subpartitions"].items()}, indent=1,
        default=str)[:1200])
    for name in report["subpartitions"]:
        comp = session.composition(name)
        f_si = session.short_lived_fraction(name, SI_GCRAM.retention_s)
        f_hy = session.short_lived_fraction(name, HYBRID_GCRAM.retention_s)
        print(f"{name}: short-lived {100 * f_si:.1f}% @Si-GC(1us) / "
              f"{100 * f_hy:.1f}% @Hy-GC(10us)   composition "
              f"{comp.summary()}")
    if out:
        session.report(out)
        print(f"report -> {out}")
    if csv_out:
        _write_composition_csv(session, csv_out)
    return report


def _write_composition_csv(session: ProfileSession, csv_out: str) -> None:
    """Machine-readable composition report (sweep CSV conventions)."""
    from repro.compose import composition_csv_rows
    comps = {name: session.composition(name)
             for name in session.report()["subpartitions"]}
    with open(csv_out, "w") as f:
        f.write("\n".join(composition_csv_rows(comps)) + "\n")
    print(f"csv -> {csv_out}")


# ---------------------------------------------------------------------------
# legacy entry points (deprecation shims over ProfileSession)
# ---------------------------------------------------------------------------

def profile_systolic(cfg, seq, dataflow, pe, out, chunk_events=None):
    session = ProfileSession("systolic")
    session.profile(transformer_gemms(cfg, seq), rows=pe, cols=pe,
                    dataflow=dataflow, chunk_events=chunk_events)
    session.analyze().compose()
    return _summarize(session, out)


def profile_gpu(cfg, seq, out, sample=8, chunk_events=None):
    session = ProfileSession("gpu")
    session.profile(transformer_program(cfg, seq), sample=sample,
                    chunk_events=chunk_events)
    session.analyze().compose()
    return _summarize(session, out)


def profile_tpu(cfg, seq, out):
    session = ProfileSession("tpu")
    session.profile(tpu_step_workload(cfg, seq), sample=4)
    session.analyze().compose()
    return _summarize(session, out)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

_DRY_SEQ = 16


def _dry_run(backend: str, policy: str = "refresh-free",
             engine: str = "numpy",
             csv_out: str | None = None) -> dict:
    """Minimal end-to-end pipeline smoke for CI: tiny built-in workload."""
    session = ProfileSession(backend)
    name = session.backend.name
    if name == "systolic":
        session.profile([GemmLayer("dry", 32, 32, 32)], rows=16, cols=16)
    elif name in ("cachesim", "opstream"):
        def program(sb):
            from repro.backends.opstream import transformer_ops
            transformer_ops(sb, d_model=64, n_heads=2, kv_heads=2,
                            d_ff=128, seq=_DRY_SEQ, n_layers=1)
        session.profile(program)
    else:  # tpu_graph
        import jax
        import jax.numpy as jnp
        x = jax.ShapeDtypeStruct((_DRY_SEQ, _DRY_SEQ), jnp.float32)
        session.profile((lambda a: (a @ a).sum(), x))
    report = session.analyze().compose(policy=policy,
                                       engine=engine).report()
    subs = report["subpartitions"]
    events = sum(v["n_reads"] + v["n_writes"] for v in subs.values())
    print(f"dry-run ok: backend={name} subpartitions={sorted(subs)} "
          f"events={events} policy={policy} engine={engine}")
    if csv_out:
        _write_composition_csv(session, csv_out)
    return report


def build_workload(arch: str, backend: str, *, seq: int | None = None,
                   smoke: bool = True):
    """Registry lowering for the CLI: ``(workload, builder_cfg)`` for any
    registered workload name, with ``seq``/``tpu_smoke`` applied when the
    spec has those params."""
    spec = get_workload(arch)
    overrides = {}
    if seq is not None and "seq" in spec.param_dict:
        overrides["seq"] = seq
    if "tpu_smoke" in spec.param_dict:
        overrides["tpu_smoke"] = smoke
    if overrides:
        spec = spec.with_params(**overrides)
    return spec.build(backend)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b",
                    help="registered workload name (see `python -m repro "
                         "workloads`)")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--backend", default="systolic",
                    choices=["systolic", "gpu", "cachesim", "opstream",
                             "tpu", "tpu_graph"])
    ap.add_argument("--dataflow", default="ws", choices=["is", "ws", "os"])
    ap.add_argument("--pe", type=int, default=128)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--out", default=None)
    ap.add_argument("--csv", default=None,
                    help="composition-report CSV path (subpartition,"
                         "policy,area_vs_sram,energy_vs_sram,"
                         "capacity_fractions)")
    ap.add_argument("--policy", default="refresh-free",
                    help="assignment policy: refresh-free | refresh-aware"
                         " | bank-quantized[:<base>][@<n_banks>]")
    ap.add_argument("--engine", default="numpy",
                    choices=("numpy", "jax"),
                    help="composition evaluation backend (jax = jitted, "
                         "~1e-9 relative energy vs the numpy oracle)")
    ap.add_argument("--chunk-events", type=int, default=None,
                    help="stream the trace to the frontend in chunks of "
                         "this many events (bounded-memory analysis)")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny built-in workload; pipeline smoke test")
    args = ap.parse_args(argv)

    compile_cache.configure()       # before the first jit
    if args.dry_run:
        return _dry_run(args.backend, policy=args.policy,
                        engine=args.engine, csv_out=args.csv)

    workload, cfg = build_workload(args.arch, args.backend, seq=args.seq,
                                   smoke=args.smoke)
    if args.backend == "systolic":
        cfg.update(rows=args.pe, cols=args.pe, dataflow=args.dataflow)
    if args.backend != "tpu" and args.backend != "tpu_graph" \
            and args.chunk_events:
        cfg["chunk_events"] = args.chunk_events
    session = ProfileSession(args.backend)
    session.profile(workload, **cfg)
    session.analyze().compose(policy=args.policy, engine=args.engine)
    return _summarize(session, args.out, args.csv)


if __name__ == "__main__":
    main()
