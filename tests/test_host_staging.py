"""Host payloads of a ref's own dtype stay on the host from the typed
call to the flush (``GlobalRef._coerce``): the same bytes as the device
path gave, no transfer at enqueue, and the bytes captured when the op
is initiated."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DartConfig, dart_exit, dart_init
from repro.core.globmem import to_bytes
from repro.core.onesided import _to_host_bytes

DTYPES = [np.dtype(np.float32), np.dtype(np.int32), np.dtype(np.uint32),
          np.dtype(jnp.bfloat16), np.dtype(np.uint8)]
#: the ref every case writes: rows 1-2 of unit 1's (4, 6) block
REF_SHAPE = (2, 6)


def _value(case, dtype):
    """The payload of ``case`` for a ref of ``REF_SHAPE`` and ``dtype``;
    every case but ``foreign`` is of ``dtype`` and stays on the host."""
    if case == "exact":
        return np.arange(12).astype(dtype).reshape(REF_SHAPE)
    if case == "scalar":
        return np.array(7).astype(dtype)
    if case == "numpy_scalar":
        return dtype.type(5)
    if case == "reshape":
        return np.arange(12).astype(dtype).reshape(3, 4)
    if case == "strided_view":
        v = np.arange(48).astype(dtype).reshape(4, 12)[::2, ::2]
        assert not v.flags.c_contiguous
        return v
    assert case == "foreign"
    return np.arange(12, dtype=np.float64).reshape(REF_SHAPE) + 0.25


def _device_coerce(value, dtype):
    """The device path every value took before: ``jnp.asarray``, then
    the three shape rules."""
    v = jnp.asarray(value, dtype=dtype)
    if v.shape == REF_SHAPE:
        return v
    if v.ndim == 0:
        return jnp.broadcast_to(v, REF_SHAPE)
    return v.reshape(REF_SHAPE)


@pytest.fixture()
def ctx():
    c = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=4096))
    yield c
    dart_exit(c)


@pytest.mark.parametrize("case", ["exact", "scalar", "numpy_scalar",
                                  "reshape", "strided_view", "foreign"])
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_same_bytes_as_the_device_path(ctx, dtype, case):
    ga = ctx.alloc((4, 6), dtype, shm=False)
    ref = ga.at[1, 1:3]
    value = _value(case, dtype)
    want = _device_coerce(value, dtype)
    out = ref._coerce(value)
    if case == "foreign":
        assert isinstance(out, jax.Array)
    else:
        assert isinstance(out, np.ndarray) and out.flags.c_contiguous
        assert not np.shares_memory(out, value)
    assert out.shape == REF_SHAPE and out.dtype == dtype
    np.testing.assert_array_equal(_to_host_bytes(out),
                                  np.asarray(to_bytes(want)))
    # and the arena holds them after a queued put
    ref.put_nb(value)
    ga.flush()
    np.testing.assert_array_equal(
        np.asarray(ref.get()).view(np.uint8),
        np.asarray(want).view(np.uint8))


@pytest.mark.parametrize("value", [np.zeros(5, np.float32),
                                   np.zeros((3, 5), np.float32),
                                   np.zeros(5, np.float64), [0.0] * 5],
                         ids=["host", "host_2d", "foreign", "list"])
def test_a_misfit_shape_raises_the_same_error(ctx, value):
    ref = ctx.alloc((4, 6), jnp.float32, shm=False).at[1, 1:3]
    with pytest.raises(ValueError, match=re.escape(
            f"value of shape {np.shape(value)} does not fit ref of "
            f"shape {REF_SHAPE}")):
        ref._coerce(value)


@pytest.mark.parametrize("op", ["put_nb", "sum", "bxor"])
def test_no_transfer_at_enqueue(ctx, op):
    """A numpy payload of the ref's dtype is queued without a copy to
    or from the device; only the flush moves it."""
    dtype = np.float32 if op != "bxor" else np.uint32
    ga = ctx.alloc((16,), dtype, shm=False)
    ref = ga.at[1, 2:7]
    value = np.arange(1, 6, dtype=dtype)
    with jax.transfer_guard("disallow_explicit"):
        h = (ref.put_nb(value) if op == "put_nb"
             else ref.accumulate(value, op))
    ga.flush()
    h.wait()
    np.testing.assert_array_equal(np.asarray(ref.get()), value)


@pytest.mark.parametrize("op", ["put_nb", "accumulate"])
def test_payload_is_captured_at_initiation(ctx, op):
    """The caller may reuse its buffer as soon as the call returns."""
    ga = ctx.alloc((16,), jnp.float32, shm=False)
    ref = ga.at[0, 4:12]
    buf = np.arange(8, dtype=np.float32)
    h = getattr(ref, op)(buf)
    buf[:] = -1
    ga.flush()
    h.wait()
    np.testing.assert_array_equal(np.asarray(ref.get()),
                                  np.arange(8, dtype=np.float32))
