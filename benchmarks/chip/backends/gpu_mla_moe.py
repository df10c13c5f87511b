"""Backend ``gpu_mla_moe``: the op-stream lowering of a DeepSeek-V2
block stack (latent attention, sparse experts with shared experts, a
leading dense layer) replayed through the L1/L2 cache simulator
(``configs/<config>.json`` ``run.backend``)."""

import numpy as np

from chipbench.manifest import decoder
from repro.backends.opstream import StreamBuilder, moe_decoder_ops


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
        from repro.core import ProfileSession
        d, run = self.dec, self.run
        with spans("lower"):
            sb = StreamBuilder(sample=run["line_sample"])
            moe_decoder_ops(
                sb, d["d_model"], d["n_heads"], run["tokens"],
                d["n_layers"], d_ff=d["d_ff"], moe_experts=d["n_experts"],
                moe_topk=d["topk"], moe_d_ff=d["moe_d_ff"],
                moe_shared_experts=d["n_shared"],
                moe_first_dense=d["first_dense"],
                kv_lora_rank=d["kv_lora_rank"],
                qk_nope_head_dim=d["qk_nope_head_dim"],
                qk_rope_head_dim=d["qk_rope_head_dim"],
                v_head_dim=d["v_head_dim"],
                route_seed=run["routing"]["seed"],
                route_skew=run["routing"]["skew"])
            t, a, w = sb.finish()
            a = a ^ np.int64(key << run["relabel_shift"])
        with spans("cachesim"):
            session = ProfileSession("gpu")
            session.profile((t, a, w), config=self.hcfg)
        return session
