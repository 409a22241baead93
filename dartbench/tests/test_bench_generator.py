"""The traffic generator: seeded, and true to each mix's description."""

import json
import pathlib

import numpy as np
import pytest

from dartbench import generator as gen

HERE = pathlib.Path(__file__).resolve().parents[1]
MIXES = ("lat-small", "rate-small", "bw-large")
SEED = 2**33 + 17           # larger than 32 bits


def _load(kind, name):
    return json.loads((HERE / kind / f"{name}.json").read_text())


def _small(name, epochs=None):
    mix = _load("traffic", name)
    if epochs:
        mix["epochs"] = epochs
    return mix, _load("configs", "dart-2u-1chip")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    mix, cfg = _small(name, epochs=192)
    a, b = gen.generate(mix, cfg, SEED), gen.generate(mix, cfg, SEED)
    c = gen.generate(mix, cfg, SEED + 1)
    for f in ("kind", "start", "unit", "lo", "length", "pstart", "pool"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert not np.array_equal(a.lo, c.lo)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_change_order_not_amount(name):
    """Stratified lengths: every seed moves the same bytes per block."""
    mix, cfg = _small(name, epochs=192)
    a, b = gen.generate(mix, cfg, SEED), gen.generate(mix, cfg, 7)
    put = a.is_kind("put")
    assert np.array_equal(put, b.is_kind("put")) or name == "lat-small"
    assert sorted(a.length[np.repeat(put, np.diff(a.start))]) == sorted(
        b.length[np.repeat(b.is_kind("put"), np.diff(b.start))])


@pytest.mark.parametrize("name", MIXES)
def test_ops_fit_their_windows(name):
    mix, cfg = _small(name, epochs=192)
    t = gen.generate(mix, cfg, SEED)
    elems = cfg["window_bytes_per_unit"] // 4
    assert t.lo.min() >= 0 and (t.lo + t.length).max() <= elems
    assert t.unit.min() >= 0 and t.unit.max() < cfg["units"]
    spec = mix["length"]
    lo = spec.get("min_elems", spec.get("elems"))
    hi = spec.get("max_elems", spec.get("elems"))
    assert t.length.min() >= lo and t.length.max() <= hi
    assert t.is_kind("put")[0]


def test_rate_epochs_never_repeat_a_slot():
    mix, cfg = _small("rate-small", epochs=64)
    t = gen.generate(mix, cfg, SEED)
    assert np.all(np.diff(t.start) == 1024)
    for e in range(t.n_epochs):
        s = t.ops_of(e)
        slots = t.unit[s] * (1 << 40) + t.lo[s]
        assert np.unique(slots).size == 1024
        assert np.all(t.lo[s] % 2 == 0)        # 8-byte aligned


def test_bw_windows_are_disjoint_round_robin():
    mix, cfg = _small("bw-large", epochs=16)
    t = gen.generate(mix, cfg, SEED)
    units = cfg["units"]
    assert [t.kind_names[k] for k in t.kind[:4]] == ["put", "get"] * 2
    for e in range(t.n_epochs):
        s = t.ops_of(e)
        if t.is_kind("put")[e]:
            assert list(t.unit[s][:units]) == list(range(units))
        for u in range(units):
            m = t.unit[s] == u
            order = np.argsort(t.lo[s][m])
            lo, ln = t.lo[s][m][order], t.length[s][m][order]
            assert np.all(lo[1:] >= lo[:-1] + ln[:-1])


@pytest.mark.parametrize("name", ("lat-small", "bw-large"))
def test_gets_read_what_an_earlier_put_wrote(name):
    mix, cfg = _small(name, epochs=192)
    t = gen.generate(mix, cfg, SEED)
    written = set()
    for e in range(t.n_epochs):
        s = t.ops_of(e)
        ranges = set(zip(t.unit[s], t.lo[s], t.length[s]))
        if t.is_kind("put")[e]:
            written |= ranges
        else:
            assert ranges <= written


def test_lat_mix_is_two_puts_to_one_get():
    mix, cfg = _small("lat-small")
    t = gen.generate(mix, cfg, SEED)
    assert np.count_nonzero(t.is_kind("put")) == 2 * np.count_nonzero(
        t.is_kind("get"))


def test_warmup_covers_every_signature():
    mix, cfg = _small("lat-small", epochs=960)
    t = gen.generate(mix, cfg, SEED)
    octave = np.ceil(np.log2(t.max_len() * 4)).astype(int)
    want = set(zip(t.kind, octave))
    warm = t.warmup_epochs()
    assert set(zip(t.kind[warm], octave[warm])) == want
    assert len(warm) <= 2 * len(want)
