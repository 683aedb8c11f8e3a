"""io tests: datasets, samplers, DataLoader (reference test strategy:
test/legacy_test/test_dataloader_* — batch shapes, order, shard coverage)."""

import numpy as np
import pytest

from paddle_tpu.io import (Dataset, IterableDataset, TensorDataset,
                           ConcatDataset, ComposeDataset, Subset, random_split,
                           SequenceSampler, RandomSampler,
                           WeightedRandomSampler, BatchSampler,
                           DistributedBatchSampler, DataLoader,
                           default_collate_fn)


class Squares(Dataset):
    def __init__(self, n):
        self.n = n

    def __getitem__(self, i):
        return np.asarray([i, i * i], dtype=np.float32)

    def __len__(self):
        return self.n


class Stream(IterableDataset):
    def __init__(self, n):
        self.n = n

    def __iter__(self):
        for i in range(self.n):
            yield {"x": np.float32(i), "y": np.float32(-i)}


def test_tensor_dataset_and_loader():
    xs = np.arange(20).reshape(10, 2).astype(np.float32)
    ys = np.arange(10).astype(np.int64)
    ds = TensorDataset([xs, ys])
    assert len(ds) == 10
    dl = DataLoader(ds, batch_size=4, drop_last=False)
    batches = list(dl)
    assert len(batches) == 3
    bx, by = batches[0]
    assert bx.shape == (4, 2) and by.shape == (4,)
    np.testing.assert_array_equal(by, [0, 1, 2, 3])
    assert batches[-1][0].shape == (2, 2)  # tail batch


def test_loader_shuffle_covers_all():
    dl = DataLoader(Squares(17), batch_size=5, shuffle=True)
    seen = np.concatenate([b[:, 0] for b in dl])
    assert sorted(seen.astype(int).tolist()) == list(range(17))


def test_loader_workers_preserve_order():
    dl0 = DataLoader(Squares(23), batch_size=4)
    dl2 = DataLoader(Squares(23), batch_size=4, num_workers=2)
    for a, b in zip(dl0, dl2):
        np.testing.assert_array_equal(a, b)


def test_iterable_dataset_loader():
    dl = DataLoader(Stream(7), batch_size=3, drop_last=True)
    batches = list(dl)
    assert len(batches) == 2
    assert set(batches[0]) == {"x", "y"}
    np.testing.assert_array_equal(batches[1]["x"], [3, 4, 5])


def test_concat_compose_subset_split():
    a, b = Squares(4), Squares(6)
    cat = ConcatDataset([a, b])
    assert len(cat) == 10
    np.testing.assert_array_equal(cat[5], b[1])
    comp = ComposeDataset([Squares(4), Squares(4)])
    item = comp[2]
    assert len(item) == 2
    sub = Subset(a, [3, 1])
    np.testing.assert_array_equal(sub[0], a[3])
    parts = random_split(Squares(10), [0.7, 0.3],
                         generator=np.random.default_rng(0))
    assert len(parts[0]) == 7 and len(parts[1]) == 3
    all_idx = sorted(parts[0].indices + parts[1].indices)
    assert all_idx == list(range(10))


def test_samplers():
    ds = Squares(10)
    assert list(SequenceSampler(ds)) == list(range(10))
    rs = list(RandomSampler(ds, generator=np.random.default_rng(0)))
    assert sorted(rs) == list(range(10))
    ws = list(WeightedRandomSampler([0, 0, 1.0], num_samples=5))
    assert ws == [2] * 5
    bs = BatchSampler(ds, batch_size=3, drop_last=True)
    assert [len(b) for b in bs] == [3, 3, 3] and len(bs) == 3


def test_distributed_batch_sampler_partitions():
    ds = Squares(10)
    seen = []
    for rank in range(2):
        s = DistributedBatchSampler(ds, batch_size=2, num_replicas=2,
                                    rank=rank)
        idx = [i for b in s for i in b]
        assert len(idx) == 5  # ceil(10/2)
        seen.extend(idx)
    assert set(seen) == set(range(10))
    # deterministic reshuffle by epoch
    s = DistributedBatchSampler(ds, batch_size=2, num_replicas=2, rank=0,
                                shuffle=True, seed=7)
    s.set_epoch(0)
    e0 = [i for b in s for i in b]
    s.set_epoch(1)
    e1 = [i for b in s for i in b]
    s.set_epoch(0)
    assert [i for b in s for i in b] == e0
    assert e0 != e1


def test_prefetch_to_device():
    import jax
    dl = DataLoader(Squares(8), batch_size=4, prefetch_to_device=True)
    b = next(iter(dl))
    assert isinstance(b, jax.Array)


def test_collate_nested():
    batch = [((np.ones(2), 1), {"a": np.zeros(3)}) for _ in range(4)]
    out = default_collate_fn(batch)
    assert out[0][0].shape == (4, 2)
    assert out[0][1].shape == (4,)
    assert out[1]["a"].shape == (4, 3)


class _BackendProbeDataset:
    """Each item reports which jax backend the process serving it sees."""

    def __len__(self):
        return 4

    def __getitem__(self, i):
        import jax
        return np.asarray([i, jax.default_backend() == "cpu"], np.int64)


@pytest.mark.parametrize("shm", [False, True])
def test_worker_process_pinned_to_cpu(monkeypatch, shm):
    """A chip belongs to one process: a loader worker process must come up
    on the CPU platform even when its environment names the TPU (here the
    variable is flipped for the children only — the parent's jax read it at
    import). Unpinned, the child would die trying to initialise 'tpu'."""
    from paddle_tpu import native
    from paddle_tpu.io import DataLoader
    if shm and not native.is_available():
        pytest.skip("native shm ring not built")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    dl = DataLoader(_BackendProbeDataset(), batch_size=2, num_workers=1,
                    multiprocessing_context="spawn", use_shared_memory=shm)
    rows = np.concatenate(list(dl))
    assert rows[:, 0].tolist() == [0, 1, 2, 3]
    assert rows[:, 1].all(), "a worker process initialised a non-CPU backend"
