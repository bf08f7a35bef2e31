"""XLA backend compiles inside the window (JAX's monitoring event
/jax/core/compile/backend_compile_duration), per simulation."""


def read(run):
    return run.compiles / len(run.sims) if run.sims else None
