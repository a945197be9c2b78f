"""Start-up layer: seconds of compilation the persistent cache did not
serve during set-up (``jax.monitoring``).  Moves ``setup_s``."""


def read(ctx):
    return ctx.setup_compile_s
