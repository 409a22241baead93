"""Share of the plans' bucket lanes that carry asked-for bytes, in
percent: the bytes the dispatched runs' ops asked for over the
``kb x seg`` lanes their plans moved, summed over the ``dart.launch``
spans of the traced stretch (program counters ``asked_bytes`` and
``lane_bytes``)."""

from dartbench import program


def read(run):
    s = program.span(run, "dart.launch")
    if s is None or not s.get("lane_bytes"):
        return None
    return 100 * s["asked_bytes"] / s["lane_bytes"]
