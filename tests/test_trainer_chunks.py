"""A chunk is on the device before its dispatch: `Trainer.fit`'s chunk producer
places the batches of a chunk, the loop stacks them on the device, and the
scanned step gets what a hand-made `np.stack` would have given it (a two-layer
encoder on the CPU's virtual devices)."""

import threading
import time

import jax
import numpy as np
import pytest

from synapseml_tpu.core import observability as obs
from synapseml_tpu.models import trainer as trainer_mod
from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_tiny
from synapseml_tpu.models.trainer import Trainer, TrainerConfig
from synapseml_tpu.parallel import MeshConfig, create_mesh

CHUNK, BATCH = 2, 8


def _batch(seed=0, B=BATCH, T=16, vocab=1024):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, (B, T)).astype(np.int32),
            "attention_mask": np.ones((B, T), np.int32),
            "labels": rng.integers(0, 2, (B,)).astype(np.int32)}


def _trainer(mesh):
    return Trainer(BertClassifier(bert_tiny(), num_classes=2), mesh,
                   TrainerConfig(total_steps=100))


def _mesh(n: int):
    return create_mesh(MeshConfig(data=n), devices=jax.devices()[:n], allow_fewer=False)


class Pulled:
    """An iterator that counts what was taken from it."""

    def __init__(self, batches):
        self._it = iter(batches)
        self.n = 0

    def __iter__(self):
        return self

    def __next__(self):
        b = next(self._it)
        self.n += 1
        return b


def _fake_scan(tr, seen, delay_s=0.0, fail_at=None):
    """Stands where the harness puts its probe: in `train_steps_scan`'s place on
    the instance. Trains nothing, so no step compiles."""

    def scan(state, stacked):
        if fail_at is not None and len(seen) == fail_at:
            raise RuntimeError("device lost")
        time.sleep(delay_s)
        seen.append(stacked)
        return state, {"loss": np.zeros(CHUNK, np.float32)}

    tr.train_steps_scan = scan


def _wait_gone(before: set, timeout_s: float = 5.0) -> list:
    """Threads alive now that were not in `before`, once they had time to end."""
    deadline = time.monotonic() + timeout_s
    while True:
        extra = [t for t in threading.enumerate() if t not in before and t.is_alive()]
        if not extra or time.monotonic() > deadline:
            return extra
        time.sleep(0.05)


# ---- (a) the same numbers as a hand-made stack ---------------------------------

@pytest.mark.parametrize("n_devices", [1, 4], ids=["one_device", "data4"])
def test_chunked_fit_is_bitwise_the_hand_stacked_scan(n_devices):
    tr = _trainer(_mesh(n_devices))
    batches = [_batch(i) for i in range(3 * CHUNK)]

    fit_losses = []
    real = tr.train_steps_scan

    def probe(state, stacked):
        out = real(state, stacked)
        fit_losses.append(out[1]["loss"])
        # what the scanned step is handed: the chunk, on the device, laid out
        # as shard_stacked_batch lays a host chunk out
        assert all(isinstance(x, jax.Array) for x in stacked.values())
        assert all(x.sharding == tr.mesh.stacked_batch_sharding()
                   for x in stacked.values())
        return out

    tr.train_steps_scan = probe
    state = tr.init_state(batches[0], jax.random.PRNGKey(0))
    fitted = tr.fit(state, iter(batches), max_steps=len(batches), scan_chunk=CHUNK)
    tr.train_steps_scan = real

    by_hand = tr.init_state(batches[0], jax.random.PRNGKey(0))
    hand_losses = []
    for i in range(0, len(batches), CHUNK):
        stacked = {k: np.stack([b[k] for b in batches[i:i + CHUNK]]) for k in batches[0]}
        by_hand, m = tr.train_steps_scan(by_hand, stacked)
        hand_losses.append(m["loss"])

    np.testing.assert_array_equal(np.concatenate([np.asarray(x) for x in fit_losses]),
                                  np.concatenate([np.asarray(x) for x in hand_losses]))
    assert int(fitted.step) == int(by_hand.step) == len(batches)
    for a, b in zip(jax.tree.leaves(fitted.params), jax.tree.leaves(by_hand.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # one program for both: a device chunk is no new signature of the step
    assert tr._scan_step._cache_size() == 2


# ---- (b) how far ahead the producer runs ------------------------------------------

def test_lookahead_is_bounded_in_batches_and_in_device_chunks(mesh_dp8, monkeypatch):
    tr = _trainer(mesh_dp8)
    n_chunks = 8
    pulled = Pulled([_batch(i) for i in range(n_chunks * CHUNK)])
    placed = [0]
    shard_batch = mesh_dp8.shard_batch

    def counting_shard_batch(batch):
        placed[0] += 1
        return shard_batch(batch)

    monkeypatch.setattr(mesh_dp8, "shard_batch", counting_shard_batch)
    seen, ahead_batches, ahead_chunks = [], [], []

    class SlowLosses:
        """Losses that take the device a while: the loop blocks fetching them,
        as it does on the chip, and the producer has all the time it wants."""

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.05)
            steps_done = (len(seen) - 1) * CHUNK          # this dispatch still runs
            ahead_batches.append(pulled.n - steps_done)
            ahead_chunks.append((placed[0] - len(seen) * CHUNK) / CHUNK)   # placed, not dispatched
            return np.zeros(CHUNK, np.float32)

    def slow(state, stacked):
        seen.append(stacked)
        return state, {"loss": SlowLosses()}

    tr.train_steps_scan = slow
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    tr.fit(state, pulled, max_steps=n_chunks * CHUNK, scan_chunk=CHUNK)
    assert len(seen) == n_chunks
    assert max(ahead_batches) <= 3 * CHUNK
    assert max(ahead_chunks) <= 2
    # and it does run ahead: a slow consumer finds both places taken
    assert max(ahead_chunks) == 2 and max(ahead_batches) == 3 * CHUNK


def test_the_producer_keeps_off_the_loop_between_two_programs(mesh_dp8):
    """While the device has nothing to run, the loop alone runs Python: the
    producer pulls and places only while the loop waits for the device."""
    tr = _trainer(mesh_dp8)
    n_chunks = 8
    pulled = Pulled([_batch(i) for i in range(n_chunks * CHUNK)])
    during_dispatch = []

    class Losses:
        def __array__(self, dtype=None, copy=None):
            time.sleep(0.05)        # the device computes; the producer fills the queue
            return np.zeros(CHUNK, np.float32)

    def scan(state, stacked):
        before = pulled.n
        time.sleep(0.05)            # a long way from the chunk to its program's enqueue
        during_dispatch.append(pulled.n - before)
        return state, {"loss": Losses()}

    tr.train_steps_scan = scan
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    tr.fit(state, pulled, max_steps=n_chunks * CHUNK, scan_chunk=CHUNK)
    assert len(during_dispatch) == n_chunks and pulled.n == n_chunks * CHUNK
    # the first program is not held back for: nothing runs on the device yet
    assert during_dispatch[1:] == [0] * (n_chunks - 1)


# ---- (c) batches that are on the device already ----------------------------------

class _NumpySpy:
    """`numpy` for the trainer module, noting every call off the main thread
    that would bring a `jax.Array` to the host."""

    PULLS = {"asarray", "array", "stack", "concatenate", "ascontiguousarray", "copy"}

    def __init__(self):
        self.main = threading.get_ident()
        self.pulled: list = []

    def __getattr__(self, name):
        attr = getattr(np, name)
        if name not in self.PULLS:
            return attr

        def guarded(*args, **kw):
            if threading.get_ident() != self.main and any(
                    isinstance(x, jax.Array) for x in jax.tree.leaves((args, kw))):
                self.pulled.append(name)
            return attr(*args, **kw)

        return guarded


def test_device_batches_are_never_brought_back_to_the_host(mesh_dp8, monkeypatch):
    obs.reset_tracer()
    tr = _trainer(mesh_dp8)
    host = [_batch(i) for i in range(2 * CHUNK)]
    on_device = [mesh_dp8.shard_batch(b) for b in host]
    spy = _NumpySpy()
    monkeypatch.setattr(trainer_mod, "np", spy)
    seen = []
    _fake_scan(tr, seen)
    state = tr.init_state(host[0], jax.random.PRNGKey(0))
    tr.fit(state, iter(on_device), max_steps=len(host), scan_chunk=CHUNK)
    monkeypatch.undo()
    assert spy.pulled == []
    assert len(seen) == 2
    for i, stacked in enumerate(seen):
        assert all(isinstance(x, jax.Array) for x in stacked.values())
        for k in host[0]:
            np.testing.assert_array_equal(
                np.asarray(stacked[k]), np.stack([b[k] for b in host[i * CHUNK:(i + 1) * CHUNK]]))
    # nothing was left to move, and every span says so
    places = [s for s in obs.get_tracer().finished_spans() if s.name == "train.place"]
    assert places and all(s.attributes["bytes"] == 0 for s in places)


# ---- (d) the loader's arrays are read, never written ------------------------------

def test_the_batches_handed_out_are_unchanged_after_the_fit(mesh_dp8):
    tr = _trainer(mesh_dp8)
    batches = [_batch(i) for i in range(2 * CHUNK + 1)]      # two chunks and a tail
    copies = [{k: v.copy() for k, v in b.items()} for b in batches]
    state = tr.init_state(batches[0], jax.random.PRNGKey(0))
    state = tr.fit(state, iter(batches), max_steps=len(batches), scan_chunk=CHUNK)
    assert int(state.step) == len(batches)
    for b, c in zip(batches, copies):
        assert set(b) == set(c)
        for k in c:
            assert isinstance(b[k], np.ndarray) and b[k].flags.writeable
            np.testing.assert_array_equal(b[k], c[k])


# ---- (e) an error on either side ends the fit, and the producer's thread ------------

def _endless():
    i = 0
    while True:
        yield _batch(i % 4)
        i += 1


def test_a_producer_error_ends_the_fit_and_its_thread(mesh_dp8):
    tr = _trainer(mesh_dp8)
    _fake_scan(tr, [], delay_s=0.01)
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))

    def batches():
        for i in range(2 * CHUNK + 1):
            yield _batch(i)
        raise ValueError("shard unreadable")

    before = set(threading.enumerate())
    with pytest.raises(ValueError, match="shard unreadable"):
        tr.fit(state, batches(), max_steps=100, scan_chunk=CHUNK)
    assert _wait_gone(before) == []
    assert tr._fit_step is None


def test_a_placement_error_in_the_producer_reaches_the_caller(mesh_dp8, monkeypatch):
    tr = _trainer(mesh_dp8)
    _fake_scan(tr, [])
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))

    def refuse(batch):
        raise MemoryError("out of device memory")

    monkeypatch.setattr(mesh_dp8, "shard_batch", refuse)
    before = set(threading.enumerate())
    with pytest.raises(MemoryError, match="out of device memory"):
        tr.fit(state, _endless(), max_steps=100, scan_chunk=CHUNK)
    assert _wait_gone(before) == []


def test_a_consumer_error_ends_the_fit_and_the_blocked_producer(mesh_dp8):
    tr = _trainer(mesh_dp8)
    seen = []
    _fake_scan(tr, seen, delay_s=0.05, fail_at=2)     # the producer is blocked in put by then
    state = tr.init_state(_batch(), jax.random.PRNGKey(0))
    pulled = Pulled(_endless())
    before = set(threading.enumerate())
    with pytest.raises(RuntimeError, match="device lost"):
        tr.fit(state, pulled, max_steps=10 ** 6, scan_chunk=CHUNK)
    assert _wait_gone(before) == []
    taken = pulled.n
    time.sleep(0.2)
    assert pulled.n == taken                            # nobody pulls any more
    assert len(seen) == 2 and tr._fit_step is None
