"""Engine step: mean host span of one serving step (dispatch to the
host's sync on its tokens) over the steps that ended in the window, from
the engine's step timeline."""


def read(run):
    return run.mean_step_ms()
