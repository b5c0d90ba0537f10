"""img_s: images answered inside the window over the window's seconds
(closed-loop cells), host clock."""


def read(run):
    if run.traffic["kind"] != "closed":
        return None
    return run.completed / run.window_s
