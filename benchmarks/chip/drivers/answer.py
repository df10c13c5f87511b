"""Traffic kind ``answer``: each request is one answer, a fresh session
profiled, analyzed and composed under every policy of the mix."""

from chipbench import traffic as gen
from chipbench.manifest import load_module
from chipbench.program import (composition_record, load_backend,
                               session_record, work_of)


class Driver:

    def __init__(self, cell, seed, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.backend = load_backend(cell.config)
        self.policies = cell.traffic["policies"]
        self.engine = cell.traffic["engine"]
        self.sample = gen.answer_sample(cell.traffic, seed)
        self.kept = None            # (index, key, session, compositions)
        self.work = None

    def _answer(self, index):
        import jax
        spans = self.spans
        key = gen.relabel_key(self.cell.traffic, self.seed, index)
        with spans("backend"):
            session = self.backend.session(key, spans)
        with spans("analyze"):
            session.analyze()
            names = list(session.report()["subpartitions"])
            for name in names:
                jax.block_until_ready(session.subpartition_stats(name)[1])
        comps = {}
        with spans("compose"):
            for policy in self.policies:
                with spans(policy):
                    session.compose(policy=policy, engine=self.engine)
                comps[policy] = {n: session.composition(n) for n in names}
        return key, session, comps

    def setup(self):
        """A warm-up answer of the window's shapes."""
        self._answer(gen.WARMUP)

    def request(self, index):
        key, session, comps = self._answer(index)
        if self.kept is None or index <= self.sample:
            self.kept = (index, key, session, comps)
        return 1

    def sampled_request(self):
        """Only the request the check samples, with no warm-up."""
        self.request(self.sample)

    def after_window(self):
        """Host copies of the sampled answer; frees the program's state."""
        index, key, session, comps = self.kept
        self.work = work_of(session)
        rec = session_record(session)
        rec["compositions"] = [composition_record(comps[p][n])
                               for p in self.policies
                               for n in rec["subs"]]
        self.kept = None
        return {"index": index, "key": key, **rec}


def reference_compositions(cell, seed, rec, ref, dtype):
    """The plain reference's compositions of the sampled answer, in the
    order of ``rec["compositions"]``: every policy of the mix over every
    subpartition, with the paper's device set."""
    comp = load_module("checks", "composition")
    devices = comp.paper_devices()
    return [comp.compose(*ref["subs"][n], devices, p,
                         clock_hz=ref["clock_hz"], dtype=dtype)
            for p in cell.traffic["policies"] for n in ref["subs"]]
