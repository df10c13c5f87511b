"""Small stand-in cells for the CPU tests."""

import os

from chipbench import manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny_cell(config, traffic, name="tiny"):
    """A cell of a small configuration under ``tests/data`` and a traffic
    mix of the benchmark, reporting every metric of BENCHMARK.json that
    applies to a cell of that traffic's kind."""
    bench = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    twin = {"answer": "chatglm3_6b.gpu.answer",
            "sweep": "chatglm3_6b.gpu.sweep"}[traffic]
    mix = manifest.load_json(os.path.join(manifest.BENCH_DIR, "traffic",
                                          traffic + ".json"))
    if traffic == "sweep":
        # at these sizes the executor's broadcast budget lets one slab
        # hold the whole grid, so only a warm-up grid of the window's
        # size compiles the window's slab; at the cell's size the budget
        # caps a slab at the 8-candidate floor, which one candidate fills
        mix["warmup_grid"] = [mix["retention_scales"], mix["area_scales"],
                              mix["energy_scales"]]
    return manifest.Cell(
        name=name, chips=1,
        config=manifest.load_json(os.path.join(DATA, config + ".json")),
        traffic=mix,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if manifest._applies(m, twin)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if manifest._applies(m, twin)))
