"""build_s: seconds of the service's `index_build` span (index build on
the host plus placing the keys), the index-build layer of set-up."""


def read(run):
    spans = [s for s in run.spans or () if s.name == "index_build"]
    return sum(s.dur for s in spans) if spans else None
