"""A put: the op's payload written at its range of a unit's window.
Blocking ``ga.at[u, lo:lo + n].put(payload)``; non-blocking ``put_nb``,
completed by the epoch's flush and wait."""

PAYLOAD = True
READS = False
RANGES = "drawn"
ENGINE_ENTRY = "put"
PLAN = "scatter"


def issue(system, u, lo, n, payload, blocking):
    ref = system.ga.at[u, lo:lo + n]
    if blocking:
        ref.put(payload)
        return None
    return ref.put_nb(payload)


def model(rows, u, lo, n, payload):
    rows[u, lo:lo + n] = payload
    return None
