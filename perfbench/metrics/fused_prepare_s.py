"""fused_prepare_s: seconds of the service's `fused_prepare` spans (the
fused executor's state fitted and verified from the host keys), a part
of the index-build layer of set-up that `build_s` does not hold."""


def read(run):
    spans = [s for s in run.spans or () if s.name == "fused_prepare"]
    return sum(s.dur for s in spans) if spans else None
