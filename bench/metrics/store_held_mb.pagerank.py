"""Store: megabytes of device memory that the written dataset's live and
retained generations hold, the ``held_bytes`` of the window's last
``store.write_layout`` span (flat while old generations retire)."""


def read(run):
    spans = [s for s in run.spans_named("store.write_layout")
             if "held_bytes" in s.args]
    if not spans:
        return None          # a program without the span
    return max(spans, key=lambda s: s.t1).args["held_bytes"] / 1e6
