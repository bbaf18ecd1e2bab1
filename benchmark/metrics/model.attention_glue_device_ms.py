"""Device time of a step in what surrounds the projections and the flash
kernels in the attention sublayers: kinds ``attention`` and
``window_attention`` less parts ``qkv`` and ``out`` and less the
instructions named ``flash_*`` (the kernels' launches): rotary, per-head
norms, relayouts, the log-sum-exp tiles, copies, all passes. Nothing where
the program's map tells no part."""

from benchmark.lib import scope_parts


def read(ctx):
    return scope_parts.ms_per_step(ctx, scope_parts.attention_glue)
