"""Find the benchmark's parts by name: ``dartbench/<group>/<name>.py``.

A later cell adds a part by adding a file; no file that is there needs
an edit.  The groups and what a module of each defines:

``ops``
    One op kind a mix issues (``put``, ``get``).  ``issue(system, u, lo,
    n, payload, blocking)`` drives the program through its public API
    (``system.ga``, ``system.ctx``) and returns the op's value (blocking)
    or its handle (non-blocking); ``model(rows, u, lo, n, payload)``
    applies the op to the plain reference's ``(units, elems)`` rows and
    returns the value it should answer.  Flags: ``PAYLOAD`` (the op
    carries data from the traffic's pool), ``READS`` (its value is
    compared), ``RANGES`` (``"drawn"`` by the mix's placement, or
    ``"written"``: the ranges of an earlier epoch of a drawn kind).
    Optional: ``ENGINE_ENTRY``, the ``CommEngine`` method a blocking op
    enters (spanned as ``enqueue`` in a traced run), and ``PLAN``, the
    segmented-copy plan it dispatches (``"scatter"``/``"gather"``, for
    the compile rehearsal).
``lengths``
    ``draw(spec, n, per_block, rng)``: ``n`` op lengths in elements from
    the mix's ``length`` spec; every block of ``per_block`` ops holds
    the same set, so seeds change the order of the work, not its amount.
``placements``
    ``place(mix, lengths, k, units, elems, rng)``: ``(unit, lo)`` of
    each drawn op; ``lengths`` holds whole epochs of ``k`` ops.
``metrics``
    ``read(run)``: one metric from a run (:class:`dartbench.run.RunView`),
    or ``None`` where the run has nothing to read for it.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def load(group: str, name: str):
    """The module ``dartbench/<group>/<name>.py``."""
    path = HERE / group / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {group} part named {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"dartbench_{group}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
