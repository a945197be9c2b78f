"""Step pipeline: the step executable's temporaries, per device
(``compiled.memory_analysis().temp_size_in_bytes``).  Moves
``peak_hbm_gib``."""


def read(ctx):
    return ctx.memory.temp_size_in_bytes / 2 ** 30
