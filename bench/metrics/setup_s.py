"""Set-up: process start to the window opening (host clock). Parameter
init, engine build, warm-up of every shape the traffic uses, and the ramp
until every slot has first held a request."""


def read(run):
    return run.setup_s
