"""Of the (query, key) pairs in the tiles the flash kernels execute for a
sliding-window layer, the share the mask keeps: the program's gauge
``ray_tpu_train_attn_window_tile_fill``, set from ``window_tile_census`` of
the pair table the step was built with (0.667 at 16384 tokens, a window of
1024 and tiles of 512; 0.800 at tiles of 256): what a change of tile or
table moves. None where the program has no such gauge (a parent without the
family)."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_attn_window_tile_fill")
