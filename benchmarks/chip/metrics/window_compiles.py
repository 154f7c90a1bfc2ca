"""Programs traced and lowered inside the measured window: jit cache
misses, counted from JAX's monitoring events whether or not the
persistent cache then supplied the executable.  Every shape is warmed in
set-up, so this reads 0 unless the program's shapes follow its input."""


def read(obs):
    return obs.window_compiles
