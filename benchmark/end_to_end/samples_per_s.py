"""Samples whose step completed in the window, over the window's seconds
(all chips together). The window runs from the call of ``train_loop`` to the
end of the first step that completes at or after ``--seconds``, so it holds
whole steps and every wait between them."""


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    return len(run.steps) * run.samples_per_step / run.window_s
