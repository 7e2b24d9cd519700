"""setup_s: seconds from process start to the window's opening, warm-up,
compiles and the idle slot's settling included (host clock)."""


def read(run):
    return run["setup_s"]
