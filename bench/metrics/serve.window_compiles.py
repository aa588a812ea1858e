"""Programs compiled or loaded from the compile cache inside the
measured window (JAX's backend-compile events); set-up should leave none."""


def read(run):
    return run.counters.get("serve.window_compiles")
