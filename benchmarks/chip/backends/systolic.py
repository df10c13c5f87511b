"""Backend ``systolic``: the GEMM list simulated on the systolic array's
scratchpads; the trace enters through ``ProfileSession.from_trace``."""

import dataclasses
from types import SimpleNamespace

import numpy as np

from chipbench.manifest import decoder


class Backend:

    def __init__(self, config):
        from repro.backends.systolic import SystolicConfig
        self.run = config["run"]
        self.scfg = SystolicConfig(**self.run["systolic"])
        self.dec = decoder(config)

    def session(self, key, spans):
        """A ``ProfileSession`` of the configuration's scratchpad trace,
        its slots XORed with ``key << relabel_shift``."""
        from repro.backends import systolic
        from repro.core import ProfileSession
        from repro.workloads.suites import transformer_gemms
        d = self.dec
        with spans("lower"):
            dims = SimpleNamespace(d_model=d["d_model"],
                                   kv_heads=d["kv_heads"], d_ff=d["d_ff"],
                                   hd=d["d_model"] // d["n_heads"])
            gemms = transformer_gemms(dims, self.run["tokens"],
                                      d["n_layers"])
        with spans("simulate"):
            trace, _ = systolic.simulate(gemms, self.scfg)
            trace = dataclasses.replace(
                trace, addr=trace.addr
                ^ np.int64(key << self.run["relabel_shift"]))
            session = ProfileSession.from_trace(trace, mode="scratchpad")
        return session
