"""Programs compiled or loaded from the compilation cache between the
window's opening and its last reply (JAX's
``/jax/core/compile/backend_compile_duration`` events)."""


def read(ctx):
    return float(len(ctx["compiles"]))
