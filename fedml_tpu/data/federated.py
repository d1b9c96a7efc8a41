"""Device-resident federated dataset containers.

The reference materializes one PyTorch ``DataLoader`` per client and returns
the 8-tuple ``[train_num, test_num, train_global, test_global,
local_num_dict, train_local_dict, test_local_dict, class_num]``
(``fedml_api/data_preprocessing/utils/partition.py:140-187``). That shape is
host-loop-centric; on TPU we want the *whole* federated dataset resident on
device as flat arrays plus a padded per-client index matrix, so a jitted
round can gather any cohort's batches with no host round-trip:

- ``x``/``y``: the global training arrays, shape ``[N, ...]``.
- ``idx``: ``[num_clients, max_n]`` int32 indices into ``x`` (padded by
  repeating index 0); ``mask`` marks real samples; ``counts`` are the true
  ``n_k`` used as FedAvg weights.

Memory cost of padding is only the int32 index matrix — the data itself is
stored once, unpadded. Batches are gathered per step inside ``lax.scan`` so
no ``[C, max_n, ...]`` tensor is ever materialized.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from fedml_tpu.data import partition as P


@struct.dataclass
class FederatedArrays:
    """Jit-friendly federated dataset (a pytree; all leaves device arrays)."""

    x: Any  # [N, ...] global train inputs
    y: Any  # [N, ...] global train targets
    idx: Any  # [num_clients, max_n] int32 into x/y
    mask: Any  # [num_clients, max_n] float32 {0,1}
    counts: Any  # [num_clients] int32 true n_k
    test_x: Any  # [M, ...] global test inputs
    test_y: Any  # [M, ...]
    test_idx: Any  # [num_clients, max_test_n] int32 into test_x
    test_mask: Any  # [num_clients, max_test_n] float32
    num_classes: int = struct.field(pytree_node=False)

    @property
    def num_clients(self) -> int:
        return self.idx.shape[0]

    @property
    def max_client_samples(self) -> int:
        return self.idx.shape[1]


def _round_up(n: int, multiple: int) -> int:
    n = max(1, n)
    if multiple > 1:
        n = ((n + multiple - 1) // multiple) * multiple
    return n


def _infer_input_dtype(x: np.ndarray):
    """Token datasets (NLP) must stay integer for nn.Embed; dense features
    go to float32."""
    return (
        jnp.int32
        if np.issubdtype(np.asarray(x).dtype, np.integer)
        else jnp.float32
    )


def _pad_index_map(
    idx_map: dict[int, np.ndarray], num_clients: int, pad_multiple: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    counts = np.array([len(idx_map[i]) for i in range(num_clients)], np.int32)
    max_n = _round_up(int(counts.max()), pad_multiple)
    idx = np.zeros((num_clients, max_n), np.int32)
    mask = np.zeros((num_clients, max_n), np.float32)
    for i in range(num_clients):
        n = counts[i]
        idx[i, :n] = idx_map[i]
        # pad with the client's OWN first sample (not global row 0): masked
        # rows contribute zero loss/grad either way, but they DO enter
        # BatchNorm batch statistics — self-padding keeps that content
        # identical between the global-array and sharded-bank layouts, so
        # the sharded runtime's equality contract extends to BN models.
        if n:
            idx[i, n:] = idx_map[i][0]
        mask[i, :n] = 1.0
    return idx, mask, counts


@dataclasses.dataclass
class FederatedData:
    """Host-side federated dataset: global numpy arrays + per-client index
    maps. Produced by the loaders, converted to :class:`FederatedArrays` for
    the compiled simulator. Mirrors the reference 8-tuple contract
    (``partition.py:186-187``) via :meth:`stats`.
    """

    x_train: np.ndarray
    y_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    train_idx_map: dict[int, np.ndarray]
    test_idx_map: dict[int, np.ndarray]
    num_classes: int
    task: str = "classification"  # "classification" | "nwp" | "tag_prediction"

    @property
    def num_clients(self) -> int:
        return len(self.train_idx_map)

    def stats(self) -> dict[str, Any]:
        train_counts = {i: len(v) for i, v in self.train_idx_map.items()}
        return {
            "train_num": int(sum(train_counts.values())),
            "test_num": int(sum(len(v) for v in self.test_idx_map.values())),
            "local_num_dict": train_counts,
            "class_num": self.num_classes,
            "class_counts": P.record_class_counts(self.y_train, self.train_idx_map),
        }

    def to_arrays(
        self, pad_multiple: int = 1, dtype=None, device: bool = True
    ) -> FederatedArrays:
        """``device=False`` keeps all leaves as host numpy arrays — used by
        the mesh-sharded runtime, whose training data lives in per-shard
        banks and whose test set is placed over the mesh instead (jit
        transfers a host leaf on use: ``evaluate_train`` there)."""
        if dtype is None:
            dtype = _infer_input_dtype(self.x_train)
        idx, mask, counts = _pad_index_map(
            self.train_idx_map, self.num_clients, pad_multiple
        )
        tidx, tmask, _ = _pad_index_map(
            self.test_idx_map, self.num_clients, pad_multiple
        )
        conv = jnp.asarray if device else np.asarray
        np_dtype = np.dtype(dtype.dtype if hasattr(dtype, "dtype") else dtype)
        return FederatedArrays(
            x=conv(self.x_train, dtype if device else np_dtype),
            y=conv(self.y_train),
            idx=conv(idx),
            mask=conv(mask),
            counts=conv(counts),
            test_x=conv(self.x_test, dtype if device else np_dtype),
            test_y=conv(self.y_test),
            test_idx=conv(tidx),
            test_mask=conv(tmask),
            num_classes=self.num_classes,
        )


@struct.dataclass
class ShardedClientBanks:
    """Per-shard sample banks for the mesh-sharded runtime: shard ``s`` owns
    clients ``[s*K, (s+1)*K)`` and ONLY their samples — per-device HBM for
    the data is ~1/n_shards of the global set (the reference keeps data
    local to silos the same way, ``fedavg_cross_silo/DistWorker.py:31-54``).

    Leading axis = shard; shard over the ``clients`` mesh axis. ``idx``
    holds LOCAL offsets into the shard's own bank."""

    x: Any  # [S, bank_max, ...]
    y: Any  # [S, bank_max, ...]
    idx: Any  # [S, K, max_n] int32 into x[s]
    mask: Any  # [S, K, max_n] float32 {0,1}

    @property
    def n_shards(self) -> int:
        return self.idx.shape[0]

    @property
    def clients_per_shard(self) -> int:
        return self.idx.shape[1]

    @property
    def max_client_samples(self) -> int:
        return self.idx.shape[2]


def shard_client_banks(
    data: "FederatedData", n_shards: int, pad_multiple: int = 1, dtype=None
) -> ShardedClientBanks:
    """Build :class:`ShardedClientBanks` from host-side federated data.
    ``max_n`` (per-client padded row length) is GLOBAL so every shard's
    local update runs the same number of steps in lockstep. The leaves
    are HOST arrays: the runtime places them over its mesh itself
    (``jax.device_put`` with the clients-axis sharding), so the whole
    set never lands on one device first."""
    n = data.num_clients
    assert n % n_shards == 0, (n, n_shards)
    K = n // n_shards
    if dtype is None:
        dtype = _infer_input_dtype(data.x_train)
    counts = np.array(
        [len(data.train_idx_map[c]) for c in range(n)], np.int64
    )
    max_n = _round_up(int(counts.max()), pad_multiple)
    bank_sizes = [
        int(counts[s * K : (s + 1) * K].sum()) for s in range(n_shards)
    ]
    bank_max = max(1, max(bank_sizes))

    sample_shape = data.x_train.shape[1:]
    y_shape = data.y_train.shape[1:]
    xb = np.zeros((n_shards, bank_max) + sample_shape, data.x_train.dtype)
    yb = np.zeros((n_shards, bank_max) + y_shape, data.y_train.dtype)
    idx = np.zeros((n_shards, K, max_n), np.int32)
    mask = np.zeros((n_shards, K, max_n), np.float32)
    for s in range(n_shards):
        off = 0
        for j in range(K):
            rows = np.asarray(data.train_idx_map[s * K + j])
            m = len(rows)
            xb[s, off : off + m] = data.x_train[rows]
            yb[s, off : off + m] = data.y_train[rows]
            idx[s, j, :m] = np.arange(off, off + m)
            # self-pad like _pad_index_map: masked rows must carry the same
            # content in both layouts (they enter BN batch statistics)
            if m:
                idx[s, j, m:] = off
            mask[s, j, :m] = 1.0
            off += m
    return ShardedClientBanks(
        x=xb.astype(np.dtype(dtype)),
        y=yb.astype(jax.dtypes.canonicalize_dtype(yb.dtype)),
        idx=idx,
        mask=mask,
    )


def arrays_and_batch(
    data: "FederatedData", dcfg, device: bool = True
) -> tuple["FederatedArrays", int]:
    """Resolve the (arrays, client batch size) pair from a DataConfig,
    honoring full-batch mode (the reference's ``batch_size=-1`` →
    ``combine_batches``, ``fedml_experiments/standalone/utils/dataset.py:158-164``).

    Every simulator should use this instead of reading
    ``dcfg.batch_size`` directly, so full-batch mode cannot be silently
    ignored by an algorithm."""
    pad = 1 if dcfg.full_batch else dcfg.batch_size
    arrays = data.to_arrays(pad_multiple=pad, device=device)
    max_n = arrays.max_client_samples
    batch = max_n if dcfg.full_batch else min(dcfg.batch_size, max_n)
    return arrays, batch


def build_federated_data(
    x_train: np.ndarray,
    y_train: np.ndarray,
    x_test: np.ndarray,
    y_test: np.ndarray,
    num_classes: int,
    num_clients: int,
    partition_method: str = "homo",
    alpha: float = 0.5,
    r: float = 1.0,
    seed: int = 0,
    task: str = "classification",
) -> FederatedData:
    """Partition global arrays into a :class:`FederatedData` (the loader
    core shared by image datasets, reference ``load_partition_data``,
    ``partition.py:140-187``)."""
    rng = np.random.default_rng(seed)
    if partition_method == "natural":
        raise ValueError("natural partitions are built by dataset loaders")
    label_y = y_train if y_train.ndim == 1 else y_train.argmax(-1)
    train_map = P.partition_indices_train(
        label_y, num_classes, partition_method, num_clients, alpha, r, rng
    )
    label_yt = y_test if y_test.ndim == 1 else y_test.argmax(-1)
    test_map = P.partition_indices_test(label_yt, num_classes, num_clients)
    return FederatedData(
        x_train, y_train, x_test, y_test, train_map, test_map, num_classes, task
    )
