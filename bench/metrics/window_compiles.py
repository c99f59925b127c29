"""Compile: programs that reached the backend inside the window
(compiled, or loaded from the persistent cache), from JAX's monitoring
events.  Set-up warms every variant the window uses, so this is 0."""


def read(run):
    return run.compile["compiles"]
