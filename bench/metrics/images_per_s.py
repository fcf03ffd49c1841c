"""``images_per_s``: images answered inside the window over the window's
seconds, all the work over all the time."""


def read(run):
    return run.completed / run.window_s
