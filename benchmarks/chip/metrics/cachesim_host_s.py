"""cachesim_host_s: host seconds per answer of the cache simulator
outside its device scans: the self time of the program's ``cachesim.*``
spans (set partition, gather, L2 stream, merge) other than
``cachesim.scan``, which holds the upload, the scan and the pull."""

from chipbench.manifest import load_module


def read(ctx):
    prog = load_module("metrics", "_program")
    got = prog.spans(ctx)
    if got is None:
        return None
    own = prog.self_s(got)
    host = [own[s["id"]] for s in got
            if s["name"].startswith("cachesim.")
            and s["name"] != "cachesim.scan"]
    return prog.per_request(ctx, sum(host)) if host else None
