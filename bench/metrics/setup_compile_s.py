"""setup_compile_s: seconds of JAX's own compile events during set-up
(tracing to a jaxpr, lowering to MLIR, and the backend compile, which
includes reads of the persistent cache)."""


def read(ctx):
    return ctx.setup_compile_s
