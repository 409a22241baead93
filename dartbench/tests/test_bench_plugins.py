"""Parts are found by name: a mix that names an op kind, a length
distribution or a placement that has a file runs with it, and one that
names a part without a file is refused before anything runs."""

import shutil
import textwrap

import jax
import pytest

from dartbench import generator, plugins, run, systems

SEED = 2**33 + 29

ACC_MAX = textwrap.dedent('''
    """An element-wise max accumulate at the target (exact in float)."""
    import numpy as np

    PAYLOAD = True
    READS = False
    RANGES = "drawn"
    ENGINE_ENTRY = "accumulate"


    def issue(system, u, lo, n, payload, blocking):
        return system.ga.at[u, lo:lo + n].accumulate(payload, "max")


    def model(rows, u, lo, n, payload):
        view = rows[u, lo:lo + n]
        np.maximum(view, payload, out=view)
        return None
''')

MIX = {"epoch_ops": 8, "blocking": False,
       "kinds": {"put": 1, "acc_max": 1, "get": 1}, "kind_order": "cycle",
       "length": {"dist": "fixed", "elems": 4},
       "placement": "distinct_slots", "lookback": 2, "epochs": 12}
CONFIG = {"units": 2, "window_bytes_per_unit": 4096,
          "world_pool_bytes_per_unit": 4096, "dtype": "float32"}


@pytest.fixture
def parts(tmp_path, monkeypatch):
    """A copy of the benchmark's parts with one op kind added as a file."""
    for group in ("ops", "lengths", "placements", "metrics"):
        shutil.copytree(plugins.HERE / group, tmp_path / group)
    (tmp_path / "ops" / "acc_max.py").write_text(ACC_MAX)
    monkeypatch.setattr(plugins, "HERE", tmp_path)
    plugins.load.cache_clear()
    yield tmp_path
    plugins.load.cache_clear()


def _run(factory):
    return run.run_cell({"name": "acc"}, CONFIG, MIX, [], seed=SEED,
                        seconds=0.3, trace=False,
                        devices=jax.devices()[:1], peaks={},
                        system_factory=factory)


def test_an_op_kind_added_as_a_file_runs_against_the_reference(parts):
    t = generator.generate(MIX, CONFIG, SEED)
    assert t.kind_names == ("put", "acc_max", "get")
    res = _run(systems.DartSystem)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0
    assert not _run(systems.LowPrecisionControl)["correct"]


@pytest.mark.parametrize("key,value", [
    ("kinds", {"put": 1, "no_such_op": 1}),
    ("placement", "no_such_placement"),
    ("length", {"dist": "no_such_dist"}),
])
def test_a_part_without_a_file_is_refused(key, value):
    mix = dict(MIX, kinds={"put": 1, "get": 1})
    with pytest.raises(FileNotFoundError, match="no_such"):
        generator.generate(dict(mix, **{key: value}), CONFIG, SEED)


def test_a_mix_of_reads_alone_is_refused():
    with pytest.raises(ValueError, match="drawn"):
        generator.generate(dict(MIX, kinds={"get": 1}), CONFIG, SEED)
