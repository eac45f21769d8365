"""Answered queries over the whole window (host clock): replies that
arrived before the window closed, over its length."""


def read(run):
    t1 = run.window.t1
    done = sum(1 for r in run.answered() if r.done <= t1)
    return done / run.window.seconds
