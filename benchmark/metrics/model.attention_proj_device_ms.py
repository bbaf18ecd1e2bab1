"""Device time of a step in the four projections of the attention
sublayers: kinds ``attention`` and ``window_attention``, parts ``qkv`` and
``out``, forward, recomputed and backward. With
``model.attention_glue_device_ms`` and the flash kernels' launches it adds
up to the two kinds' time. Nothing where the program's map tells no
part."""

from benchmark.lib import scope_parts


def read(ctx):
    return scope_parts.ms_per_step(ctx, scope_parts.attention_projection)
