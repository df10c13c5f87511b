"""Traffic kind ``sweep``: set-up profiles and analyzes one session;
each request is then one ``session.sweep`` over a freshly drawn grid."""

from chipbench import traffic as gen
from chipbench.manifest import load_module
from chipbench.program import (composition_record, load_backend,
                               session_record, work_of)


class Driver:

    def __init__(self, cell, seed, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.backend = load_backend(cell.config)
        self.traffic = cell.traffic
        self.results = []
        self.work = None

    def grid(self, scales):
        from repro.sweep import DeviceGrid
        r, a, e = scales
        return DeviceGrid(mixes=tuple(self.traffic["mixes"]),
                          retention_scales=r, area_scales=a,
                          energy_scales=e,
                          include_sram_only=self.traffic["include_sram_only"])

    def _sweep(self, scales):
        with self.spans("sweep"):
            return self.session.sweep(self.grid(scales),
                                      policy=self.traffic["policy"],
                                      engine=self.traffic["engine"],
                                      attach=False)

    def _profile(self):
        import jax
        self.key = gen.relabel_key(self.traffic, self.seed, gen.SETUP)
        self.session = self.backend.session(self.key, self.spans)
        self.session.analyze()
        for name in self.session.report()["subpartitions"]:
            jax.block_until_ready(self.session.subpartition_stats(name)[1])

    def setup(self):
        """The profiled session, then a warm-up sweep over the smaller
        grid ``warmup_scales`` sizes (gen.warmup_scales)."""
        self._profile()
        self._sweep(gen.warmup_scales(self.traffic, self.seed))

    def request(self, index):
        res = self._sweep(gen.grid_scales(self.traffic, self.seed, index))
        self.results.append(res)
        return len(res.points)

    def sampled_request(self):
        """Only the request the check samples, with no warm-up."""
        self._profile()
        self.request(0)

    def after_window(self):
        """Host copies of the session and of every composition of the
        sampled sweep; frees the program's state."""
        k = gen.sweep_sample(self.traffic, self.seed, len(self.results))
        points = self.results[k].points
        work = work_of(self.session)
        grid = self.grid(gen.grid_scales(self.traffic, self.seed, k))
        work["devices"] = [len(c.devices) for c in grid.candidates()]
        self.work = work
        rec = session_record(self.session)
        rec.update(index=k, key=self.key,
                   compositions=[composition_record(p.composition)
                                 for p in points])
        self.results = []
        self.session = None
        return rec


def reference_compositions(cell, seed, rec, ref, dtype):
    """The plain reference's compositions of the sampled sweep, in the
    order of its points: every candidate of the grid over each
    subpartition in turn."""
    comp = load_module("checks", "composition")
    tr = cell.traffic
    cands = comp.grid_candidates(tr["mixes"],
                                 *gen.grid_scales(tr, seed, rec["index"]))
    if not tr["include_sram_only"]:
        cands = cands[1:]
    return [comp.compose(*ref["subs"][n], c, tr["policy"],
                         clock_hz=ref["clock_hz"], dtype=dtype)
            for n in ref["subs"] for c in cands]
