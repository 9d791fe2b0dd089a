"""Process start to the window's opening (host clock): weights, engine,
compilation or cache loads, warm-up runs and set-up sessions."""


def read(run):
    r = run.record
    return r.t0 - r.t_start
