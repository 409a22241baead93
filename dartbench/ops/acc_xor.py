"""An HPCC RandomAccess update: ``T[i] ^= word`` on the 64-bit words of
an op's range, as one XOR accumulate at the target.  Non-blocking
``ga.at[u, lo:lo + n].accumulate(words, "bxor")``, completed by the
epoch's flush and wait; blocking, the same followed by its wait.

The traffic's payload pool is standard normals cast to the dtype, which
for uint32 is mostly 0 and small integers.  ``issue`` and ``model``
both turn the drawn payload into full-width words by :func:`words`, one
fixed integer mix of the payload, each element's global index and the
update's position in the stream of updates its table has taken.  HPCC's
own stream couples value and address in the same way and never repeats
an update.  So no update is a no-op XOR of 0, a payload changed in a
lower precision changes its word, and an epoch issued twice (the
warm-up epoch, which ``drive.py`` issues again as the window's first)
does not XOR itself away and leave its words unchecked."""

import weakref

import numpy as np

PAYLOAD = True
READS = False
RANGES = "drawn"
ENGINE_ENTRY = "accumulate"

#: id(table) -> [weak reference to it, updates it has taken]
_STREAM = {}


def position(table):
    """How many updates ``table`` (a system, or the reference's rows)
    has taken before this one, counting this one in."""
    key = id(table)
    entry = _STREAM.get(key)
    if entry is None or entry[0]() is not table:
        entry = _STREAM[key] = [
            weakref.ref(table, lambda _, k=key: _STREAM.pop(k, None)), 0]
    entry[1] += 1
    return entry[1] - 1


def words(payload, u, lo, elems, pos):
    """The update's elements: ``payload`` plus a multiple of each
    element's global index ``u * elems + lo + j`` and of the stream
    position ``pos``, then one xorshift and odd multiply round, all
    modulo 2**32.  For a fixed index and position each step is a
    bijection of the payload, so distinct payloads give distinct
    words."""
    x = np.arange(lo, lo + payload.size, dtype=np.uint32)
    x += np.uint32((u * elems + pos * 0x632BE5AB) & 0xFFFFFFFF)
    x *= np.uint32(0x9E3779B1)
    x += payload.astype(np.uint32)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x85EBCA6B)
    return x


def issue(system, u, lo, n, payload, blocking):
    h = system.ga.at[u, lo:lo + n].accumulate(
        words(payload, u, lo, system.elems, position(system)), "bxor")
    if blocking:
        h.wait()
        return None
    return h


def model(rows, u, lo, n, payload):
    rows[u, lo:lo + n] ^= words(payload, u, lo, rows.shape[1],
                                position(rows))
    return None
