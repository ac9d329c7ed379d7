"""The device backends' packed inputs: each batch reaches the device as
one ``(3, cap)`` int32 host array handed to the jitted join, on a
full-height layout and on a shard's row window, with the answers of the
host merge join."""
import numpy as np
import pytest

from repro.core.device_index import DeviceIndex
from repro.graphgen import erdos_renyi
from repro.obs import Observability
from repro.service import BatchExecutor, RLCService, ServiceConfig

V = 48
LO, HI = 12, 36          # the row window a shard of the middle holds


@pytest.fixture(scope="module")
def svc():
    g = erdos_renyi(V, 3.0, 3, seed=4)
    return RLCService.build(g, ServiceConfig(k=2, batch_size=32,
                                             cache_capacity=0))


def _executor(svc, layout, obs=None):
    if layout == "full":
        frozen, di = svc.frozen, svc.device_index
    else:
        frozen = svc.frozen.slice_rows(LO, HI)
        di = DeviceIndex.from_frozen(frozen, svc.mr_ids, rows=(LO, HI))
        assert di.row_lo == LO and di.out_hub.shape[0] == HI - LO
    return BatchExecutor(svc.index, frozen, device_index=di,
                         id_to_mr=svc._id_to_mr, obs=obs)


def _batch(svc, layout, seed, size=32):
    lo, hi = (0, V) if layout == "full" else (LO, HI)
    rng = np.random.default_rng(seed)
    s = rng.integers(lo, hi, size).astype(np.int32)
    t = rng.integers(lo, hi, size).astype(np.int32)
    mr = rng.integers(0, len(svc.mr_ids), size).astype(np.int32)
    return s, t, mr


@pytest.mark.parametrize("layout", ["full", "window"])
@pytest.mark.parametrize("fill", [1, 5, 32])
@pytest.mark.parametrize("backend", ["pallas", "sorted"])
def test_packed_batch_answers_as_numpy(svc, backend, fill, layout):
    ex = _executor(svc, layout)
    # a 32-slot batch of which the first `fill` are real: the executor
    # packs and pads only those
    s, t, mr = _batch(svc, layout, seed=fill)
    got, b = ex.execute(s, t, mr, n_real=fill, backend=backend)
    ref, rb = ex.execute(s, t, mr, n_real=fill, backend="numpy")
    assert (b, rb) == (backend, "numpy")
    assert got.shape == (fill,)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("layout", ["full", "window"])
def test_packed_batches_cover_true_and_false(svc, layout):
    """The batches above are no vacuous all-False check."""
    ex = _executor(svc, layout)
    ref, _ = ex.execute(*_batch(svc, layout, seed=32), backend="numpy")
    assert ref.any() and not ref.all()


def test_pack_pow2_pads_with_slot_zero():
    s = np.array([3, 4, 5, 6, 7], np.int64)
    q = BatchExecutor._pack_pow2(s, s + 10, s + 20, 3)
    assert q.dtype == np.int32 and q.flags.c_contiguous
    np.testing.assert_array_equal(
        q, [[3, 4, 5, 3], [13, 14, 15, 13], [23, 24, 25, 23]])


def test_h2d_arrays_one_per_device_batch(svc):
    obs = Observability()
    ex = _executor(svc, "full", obs=obs)
    s, t, mr = _batch(svc, "full", seed=7)
    runs = {"pallas": 3, "sorted": 2, "numpy": 2, "python": 1}
    for backend, n in runs.items():
        for fill in (1, 5, 32)[:n]:
            ex.execute(s, t, mr, n_real=fill, backend=backend)
    reg = obs.registry
    h2d = reg.get("rlc_executor_h2d_arrays")
    bat = reg.get("rlc_executor_batches")
    for backend, n in runs.items():
        lab = dict(backend=backend, shard="-")
        assert bat.value(**lab) == n
        want = n if backend in ("pallas", "sorted") else 0
        assert h2d.value(**lab) == want, backend


class _Clock:
    """``time`` for the executor module: ``perf_counter`` reads the
    scripted instants in order."""

    def __init__(self, *instants):
        self.instants = list(instants)

    def perf_counter(self):
        return self.instants.pop(0)


@pytest.mark.parametrize("backend", ["pallas", "sorted", "numpy", "python"])
def test_batch_seconds_are_dispatch_plus_result(svc, backend, monkeypatch):
    """``rlc_executor_batch_seconds`` records a batch once, when it is
    answered: the time in dispatch plus the time in ``result()``, not the
    time between them."""
    import repro.service.executor as executor_mod
    obs = Observability()
    ex = _executor(svc, "full", obs=obs)
    s, t, mr = _batch(svc, "full", seed=9)
    ref, _ = _executor(svc, "full").execute(s, t, mr, n_real=5,
                                            backend="numpy")
    monkeypatch.setattr(executor_mod, "time", _Clock(0.0, 1.0, 10.0, 12.0))
    pending = ex.dispatch(s, t, mr, n_real=5, backend=backend)
    assert pending.done() or backend in ("pallas", "sorted")
    got, b = pending.result()
    again, _ = pending.result()              # answered once, accounted once
    assert b == backend and got.shape == (5,)
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(again, got)
    cell = obs.registry.get("rlc_executor_batch_seconds").labels(
        backend=backend, shard="-")
    assert cell.reservoir.count == 1 and cell.reservoir.total == 3.0
    assert obs.registry.get("rlc_executor_batches").value(
        backend=backend, shard="-") == 1
    assert ex.recorders[backend].batches == 1


def test_failed_result_raises_and_accounts_nothing(svc):
    """A readback that fails raises :class:`ExecutorError` from
    ``result()``, as a failed dispatch does: the batch is not retried on
    a host backend, and nothing counts it as answered."""
    from repro.service import ExecutorError

    class Unready:
        def copy_to_host_async(self):
            pass

        def is_ready(self):
            return False

        def block_until_ready(self):
            raise RuntimeError("device lost")

    class LostReadback:
        row_len = 8

        def join(self, q, method):
            return Unready()

    obs = Observability()
    ex = BatchExecutor(svc.index, svc.frozen, device_index=LostReadback(),
                       id_to_mr=svc._id_to_mr, backend="sorted", obs=obs)
    s, t, mr = _batch(svc, "full", seed=11)
    pending = ex.dispatch(s, t, mr, n_real=6)
    assert not pending.done()
    with pytest.raises(ExecutorError, match="'sorted' failed") as err:
        pending.result()
    assert isinstance(err.value.__cause__, RuntimeError)
    assert ex.fallbacks == 0 and ex.stats() == {}
    assert obs.registry.get("rlc_executor_h2d_arrays").value(
        backend="sorted", shard="-") == 1
    assert obs.registry.get("rlc_executor_batches").value(
        backend="sorted", shard="-") == 0
