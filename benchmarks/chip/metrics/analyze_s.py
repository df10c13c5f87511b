"""analyze_s: host seconds per answer inside the benchmark's ``analyze``
span (see chipbench/program.py for what the span encloses)."""


def read(ctx):
    return ctx.span_per_request("analyze")
