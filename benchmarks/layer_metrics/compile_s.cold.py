"""fragment compile (exec/compilesvc.py): seconds JAX spent compiling or
loading programs during set-up, from JAX's monitoring events
(/jax/core/compile/backend_compile_duration).  Minutes in a fresh checkout,
a second or two per statement once the persistent cache holds them."""


def read(ctx):
    return float(ctx["setup"]["compile_s"])
