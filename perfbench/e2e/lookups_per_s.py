"""lookups_per_s: keys answered inside the window over the window's
seconds (host clock).  Requests drained after the close do not count."""


def read(run):
    return run.answered_in_window / run.seconds
