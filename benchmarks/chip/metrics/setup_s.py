"""setup_s: seconds from process start to the first timed request -
imports, device start-up, building the program's inputs, compiling or
loading every program from the persistent cache, and the warm-up
request."""


def read(ctx):
    return ctx.setup_s
