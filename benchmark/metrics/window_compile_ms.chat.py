"""LM server: seconds of the window in which JAX compiled a program or
loaded one from the persistent cache (its own `backend_compile_duration`
events): the packed readback's eager `jnp.concatenate`, the one function
the configuration tolerates there. The serving thread waits for each."""


def read(run):
    return 1000.0 * run["compiled_in_window"]["seconds"]
