"""Mean number of active slots in each decode step of the traced window,
counted from the batcher's active mask just before each step."""


def read(run):
    steps = run.record.steps if run.record else []
    if not steps:
        return None
    return sum(len(c) for c in steps) / len(steps)
