"""Active lane-steps the wave engine executed over the window's wall
(the corpus explorer's `device_steps`, the engine's lane-step counter)."""


def read(run):
    if run.get("device_steps") is None or not run.get("wall_s"):
        return None
    return run["device_steps"] / run["wall_s"]
