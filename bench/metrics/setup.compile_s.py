"""setup.compile_s: seconds of backend compilation in set-up, from JAX's
own compile events (0 when every program came from the persistent
cache)."""


def read(ctx):
    return ctx["setup_compile_s"]
