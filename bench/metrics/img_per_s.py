"""Images whose logits reached the host in the window, over the window's
seconds: every query of the closed loop, the one in flight at the deadline
included, and all the time they took (staging and refills too)."""


def read(run):
    if run.images == 0 or run.window_s <= 0:
        return None
    return run.images / run.window_s
