"""Store: padded over valid bytes of the layouts the window wrote, from
the store's ``write_log`` (what key skew costs the layout)."""


def read(run):
    valid = sum(e.get("valid_bytes", 0) for e in run.write_log)
    if not valid:
        return None
    return sum(e.get("padded_bytes", 0) for e in run.write_log) / valid
