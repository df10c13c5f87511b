"""Backend ``gpu``: the op-stream lowering replayed through the L1/L2
cache simulator (``configs/<config>.json`` ``run.backend``)."""

import numpy as np

from chipbench.manifest import decoder


class Backend:

    def __init__(self, config):
        from repro.backends.cachesim import CacheConfig, HierarchyConfig
        run = config["run"]
        h = run["hierarchy"]
        self.hcfg = HierarchyConfig(
            l1=CacheConfig(**h["l1"]), l2=CacheConfig(**h["l2"]),
            write_allocate=h["write_allocate"], clock_hz=h["clock_hz"],
            l2_latency=h["l2_latency"])
        self.dec = decoder(config)
        self.run = run

    def session(self, key, spans):
        """A profiled ``ProfileSession`` of the configuration's stream,
        its addresses XORed with ``key << relabel_shift``."""
        from repro.backends.opstream import StreamBuilder, transformer_ops
        from repro.core import ProfileSession
        d = self.dec
        with spans("lower"):
            sb = StreamBuilder(sample=self.run["line_sample"])
            transformer_ops(sb, d["d_model"], d["n_heads"], d["kv_heads"],
                            d["d_ff"], self.run["tokens"],
                            n_layers=d["n_layers"])
            t, a, w = sb.finish()
            a = a ^ np.int64(key << self.run["relabel_shift"])
        with spans("cachesim"):
            session = ProfileSession("gpu")
            session.profile((t, a, w), config=self.hcfg)
        return session
