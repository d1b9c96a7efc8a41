"""The benchmark's traffic generator: one general function that turns a
configuration file (dataset shape), a traffic file (population, client-
size law, cohort) and ``--seed`` into the federated population the
program is handed.

Stand-in data: class prototypes plus unit noise at the dataset's real
shape and cardinality (the arithmetic of ``fedml_tpu.data.loaders.
_fake_image_arrays``, copied so that the program may change and the
yardstick may not). The chip machine has no data files.

What the seed moves and what it does not. A cell's WORK is fixed by its
traffic file: the client-size vector and every client's label histogram
come from the file's own ``partition.seed`` and are the same for every
``--seed``. ``--seed`` draws the pixel values, decides which sample
positions make up each client, and (in ``run.py``) the initial weights.
So every seed runs the same set of client sizes on different data, and
rates do not swing with the draw of a Dirichlet vector.
"""

from __future__ import annotations

import numpy as np

MIN_PARTITION_SIZE = 10  # the reference LDA partitioner's retry threshold


def class_of(n: int, classes: int) -> np.ndarray:
    """Class of abstract item ``a`` in a perfectly balanced set (CIFAR's
    train split is balanced): items ``[k*per, (k+1)*per)`` are class k."""
    if n % classes:
        raise ValueError(f"{n} samples do not split evenly over {classes} classes")
    return np.repeat(np.arange(classes, dtype=np.int32), n // classes)


def _lda_items(n, classes, clients, alpha, rng):
    """Dirichlet-LDA split of the abstract items with the reference's
    balancing rule (a client at or over N/clients takes no share of the
    later classes) and its min-size-10 retry
    (``fedml_core/non_iid_partition/noniid_partition.py``; same
    arithmetic as ``fedml_tpu/data/partition.py``)."""
    per = n // classes
    target = n / clients
    while True:
        batches = [[] for _ in range(clients)]
        for k in range(classes):
            items = k * per + rng.permutation(per)
            props = rng.dirichlet(np.repeat(alpha, clients))
            props = props * np.array([len(b) < target for b in batches])
            props = props / props.sum()
            cuts = (np.cumsum(props) * per).astype(int)[:-1]
            for b, part in zip(batches, np.split(items, cuts)):
                b.extend(part.tolist())
        if min(len(b) for b in batches) >= MIN_PARTITION_SIZE:
            return [np.asarray(b, np.int64) for b in batches]


def _shard_items(n, classes, clients, per_client, shard, rng):
    """Every client holds exactly ``per_client`` samples, as label-sorted
    shards of ``shard`` samples dealt at random (McMahan et al. 2017's
    shard split): fixed sizes, few classes a client."""
    if clients * per_client != n or per_client % shard:
        raise ValueError((n, clients, per_client, shard))
    order = rng.permutation(n // shard)
    dealt = order.reshape(clients, per_client // shard)
    return [
        (d[:, None] * shard + np.arange(shard)[None, :]).reshape(-1)
        for d in dealt
    ]


def client_items(partition: dict, n: int, classes: int, clients: int):
    """The traffic file's client-size law -> one array of abstract item
    ids per client. Depends on the file alone, never on ``--seed``."""
    rng = np.random.default_rng(int(partition.get("seed", 0)))
    law = partition["law"]
    if law == "lda":
        return _lda_items(n, classes, clients, float(partition["alpha"]), rng)
    if law == "shards":
        return _shard_items(
            n, classes, clients, int(partition["samples_per_client"]),
            int(partition["shard"]), rng,
        )
    raise ValueError(f"unknown client-size law {law!r}")


def make_population(dataset: dict, traffic: dict, seed: int):
    """-> dict of host arrays: ``x_train, y_train, x_test, y_test``,
    ``train_map`` / ``test_map`` (client -> sample positions) and
    ``sizes``. ``dataset``: ``input_shape, classes, n_train, n_test``."""
    shape = tuple(dataset["input_shape"])
    classes = int(dataset["classes"])
    n_train, n_test = int(dataset["n_train"]), int(dataset["n_test"])
    clients = int(traffic["population"])
    items = client_items(traffic["partition"], n_train, classes, clients)

    rng = np.random.default_rng(seed)
    protos = rng.standard_normal((classes,) + shape, np.float32)

    def draw(n):
        # abstract item a lives at position place[a]; its class is fixed
        place = rng.permutation(n)
        y = np.empty(n, np.int32)
        y[place] = class_of(n, classes)
        x = rng.standard_normal((n,) + shape, np.float32)
        x += 0.5 * protos[y]
        return x, y, place

    x_tr, y_tr, place = draw(n_train)
    x_te, y_te, _ = draw(n_test)
    train_map = {c: rng.permutation(place[it]) for c, it in enumerate(items)}
    test_map = dict(enumerate(np.array_split(np.arange(n_test), clients)))
    return {
        "x_train": x_tr, "y_train": y_tr, "x_test": x_te, "y_test": y_te,
        "train_map": train_map, "test_map": test_map, "classes": classes,
        "sizes": np.array([len(it) for it in items], np.int64),
    }
