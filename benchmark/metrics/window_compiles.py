"""Compiles inside the window: the persistent compilation cache's
misses JAX reported there. Set-up compiles every shape the window uses,
or a run in the same checkout did, so this reads 0; an executable the
window loads from the cache is a hit, not a compile."""


def read(run):
    compiles = run.get("compiles")
    if compiles is None:
        return None
    return float(compiles["misses"])
