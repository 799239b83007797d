"""Output tokens delivered to the host in the window, over the window.
A token counts once: a preempted request that generates tokens again
delivers only those past what it had delivered."""


def read(run, name):
    n = sum(run.W0 <= t < run.W1 for r in run.recs.values()
            for t in r.stamps)
    return n / run.seconds
