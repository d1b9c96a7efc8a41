"""Federated dataset loaders.

The reference ships 21 loader packages (SURVEY.md §2.5), each returning the
8-tuple. Here every loader returns a :class:`FederatedData`. Two families:

- **Real-file loaders** (``mnist``, ``cifar10``, ``cifar100``, ``cinic10``,
  ``femnist``, ``shakespeare``): parse the standard on-disk formats (IDX,
  CIFAR pickles, LEAF json, raw text) when present under ``data_dir``
  (reference download scripts: ``data/<ds>/download_*.sh``).
- **Procedural datasets** for offline/CI use: ``synthetic`` reproduces the
  LEAF/FedProx ``synthetic(a,b)`` generator the reference ships as
  ``data/synthetic_*/generate_synthetic.py``; ``fake_<name>`` generates a
  deterministic *learnable* stand-in with the exact shapes/cardinalities of
  the named dataset (gaussian class prototypes + noise) — the moral
  equivalent of the reference's CI tiny-runs (``CI-script-fedavg.sh:36-43``)
  without requiring downloads.
"""

from __future__ import annotations

import gzip
import os
import pickle
import struct as pystruct

import numpy as np

from fedml_tpu.config import DataConfig
from fedml_tpu.data import partition as P
from fedml_tpu.data.federated import FederatedData, build_federated_data

# name -> (input_shape, num_classes) for image datasets
IMAGE_SPECS: dict[str, tuple[tuple[int, ...], int]] = {
    "mnist": ((28, 28, 1), 10),
    "emnist": ((28, 28, 1), 62),
    "femnist": ((28, 28, 1), 62),
    "cifar10": ((32, 32, 3), 10),
    "cifar100": ((32, 32, 3), 100),
    "cinic10": ((32, 32, 3), 10),
    "fed_cifar100": ((32, 32, 3), 100),
}

SHAKESPEARE_SEQ_LEN = 80  # reference char-LM window (model/nlp/rnn.py:4-37)
SHAKESPEARE_VOCAB = 90
STACKOVERFLOW_SEQ_LEN = 20
STACKOVERFLOW_VOCAB = 10000
STACKOVERFLOW_TAGS = 500


# ---------------------------------------------------------------------------
# Procedural datasets (offline / CI)
# ---------------------------------------------------------------------------


def make_synthetic(
    num_clients: int,
    alpha: float = 1.0,
    beta: float = 1.0,
    dim: int = 60,
    num_classes: int = 10,
    samples_low: int = 50,
    samples_high: int = 500,
    seed: int = 0,
) -> FederatedData:
    """LEAF/FedProx ``synthetic(alpha, beta)``: per-client logistic model
    ``y = argmax(softmax(W_k x + b_k))`` with ``W_k ~ N(u_k, 1)``,
    ``u_k ~ N(0, alpha)``, ``x ~ N(v_k, Sigma)``, ``v_k ~ N(B_k, 1)``,
    ``B_k ~ N(0, beta)`` — naturally non-IID in both model and features
    (reference generator: ``data/synthetic_1_1/generate_synthetic.py``).
    """
    rng = np.random.default_rng(seed)
    sizes = (
        np.minimum(
            rng.lognormal(4.0, 2.0, num_clients).astype(int) + samples_low,
            samples_high,
        )
    )
    sigma = np.diag(np.arange(1, dim + 1, dtype=np.float64) ** -1.2)
    xs, ys, train_map, test_map = [], [], {}, {}
    off = 0
    for k in range(num_clients):
        u_k = rng.normal(0, alpha)
        b_center = rng.normal(0, beta)
        W = rng.normal(u_k, 1.0, (dim, num_classes))
        b = rng.normal(u_k, 1.0, num_classes)
        v_k = rng.normal(b_center, 1.0, dim)
        n = int(sizes[k])
        x = rng.multivariate_normal(v_k, sigma, n).astype(np.float32)
        logits = x @ W + b
        y = logits.argmax(-1).astype(np.int32)
        xs.append(x)
        ys.append(y)
        n_train = max(1, int(0.9 * n))
        train_map[k] = np.arange(off, off + n_train)
        test_map[k] = np.arange(off + n_train, off + n)
        off += n
    x_all = np.concatenate(xs)
    y_all = np.concatenate(ys)
    # train/test share the flat arrays; index maps disjoint
    test_idx = np.concatenate([test_map[k] for k in range(num_clients)])
    # re-base the test index map onto the test arrays
    remap = {int(g): i for i, g in enumerate(test_idx)}
    test_map = {
        k: np.array([remap[int(g)] for g in v], np.int64)
        for k, v in test_map.items()
    }
    return FederatedData(
        x_train=x_all,
        y_train=y_all,
        x_test=x_all[test_idx],
        y_test=y_all[test_idx],
        train_idx_map=train_map,
        test_idx_map=test_map,
        num_classes=num_classes,
    )


def _fake_image_arrays(
    name: str, n_train: int, n_test: int, seed: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    shape, num_classes = IMAGE_SPECS[name]
    rng = np.random.default_rng(seed)
    protos = rng.normal(0.0, 1.0, (num_classes,) + shape).astype(np.float32)

    def gen(n):
        y = rng.integers(0, num_classes, n).astype(np.int32)
        x = protos[y] * 0.5 + rng.normal(0, 1.0, (n,) + shape).astype(np.float32)
        return x.astype(np.float32), y

    x_tr, y_tr = gen(n_train)
    x_te, y_te = gen(n_test)
    return x_tr, y_tr, x_te, y_te, num_classes


def make_fake_image_dataset(
    name: str, cfg: DataConfig, n_train: int = 6000, n_test: int = 1000
) -> FederatedData:
    x_tr, y_tr, x_te, y_te, num_classes = _fake_image_arrays(
        name, n_train, n_test, cfg.seed
    )
    return build_federated_data(
        x_tr, y_tr, x_te, y_te, num_classes, cfg.num_clients,
        cfg.partition_method, cfg.partition_alpha, cfg.dataset_r, cfg.seed,
    )


def make_fake_text_dataset(
    cfg: DataConfig,
    seq_len: int = SHAKESPEARE_SEQ_LEN,
    vocab: int = SHAKESPEARE_VOCAB,
    n_train: int = 4000,
    n_test: int = 500,
) -> FederatedData:
    """Markov-chain token sequences for next-word/char prediction (stand-in
    for shakespeare / stackoverflow_nwp)."""
    rng = np.random.default_rng(cfg.seed)
    # sparse markov transition: each token has 8 likely successors — gives an
    # LM something learnable.
    succ = rng.integers(0, vocab, (vocab, 8))

    def gen(n):
        seq = np.zeros((n, seq_len + 1), np.int32)
        seq[:, 0] = rng.integers(0, vocab, n)
        for t in range(seq_len):
            choice = succ[seq[:, t], rng.integers(0, 8, n)]
            noise = rng.integers(0, vocab, n)
            take_noise = rng.random(n) < 0.1
            seq[:, t + 1] = np.where(take_noise, noise, choice)
        return seq[:, :-1], seq[:, 1:]

    x_tr, y_tr = gen(n_train)
    x_te, y_te = gen(n_test)
    # partition homo over sequence index (labels are sequences; LDA undefined)
    rng2 = np.random.default_rng(cfg.seed + 1)
    perm = rng2.permutation(n_train)
    train_map = {
        i: s for i, s in enumerate(np.array_split(perm, cfg.num_clients))
    }
    test_map = {
        i: s
        for i, s in enumerate(
            np.array_split(np.arange(n_test), cfg.num_clients)
        )
    }
    return FederatedData(
        x_tr, y_tr, x_te, y_te, train_map, test_map, vocab, task="nwp"
    )


def make_fake_segmentation_dataset(
    cfg: DataConfig,
    img_size: int = 32,
    num_classes: int = 4,
    n_train: int = 512,
    n_test: int = 64,
) -> FederatedData:
    """Procedural segmentation data (stand-in for pascal_voc/coco in the
    reference fedseg path): each image contains axis-aligned class blobs on
    background 0; the mask is the generating layout, so the task is
    learnable by a small encoder-decoder."""
    rng = np.random.default_rng(cfg.seed)

    def gen(n):
        x = rng.normal(0, 0.1, (n, img_size, img_size, 3)).astype(np.float32)
        y = np.zeros((n, img_size, img_size), np.int32)
        for i in range(n):
            for c in range(1, num_classes):
                cx, cy = rng.integers(0, img_size, 2)
                h, w = rng.integers(img_size // 4, img_size // 2, 2)
                y[i, cx:cx + h, cy:cy + w] = c
                x[i, cx:cx + h, cy:cy + w, :] += np.eye(3)[c % 3] * c
        return x, y

    x_tr, y_tr = gen(n_train)
    x_te, y_te = gen(n_test)
    # a pixel mask has no single image label; partition on the per-image
    # MAJORITY class so hetero-LDA still has a label signal to skew on
    rng2 = np.random.default_rng(cfg.seed)

    def majority(y):
        flat = y.reshape(y.shape[0], -1)
        return np.array(
            [np.bincount(r, minlength=num_classes).argmax() for r in flat],
            np.int64,
        )

    train_map = P.partition_indices_train(
        majority(y_tr), num_classes, cfg.partition_method, cfg.num_clients,
        cfg.partition_alpha, cfg.dataset_r, rng2,
    )
    test_map = P.partition_indices_test(
        majority(y_te), num_classes, cfg.num_clients
    )
    return FederatedData(
        x_tr, y_tr, x_te, y_te, train_map, test_map, num_classes,
        task="segmentation",
    )


def make_fake_tag_dataset(
    cfg: DataConfig,
    vocab: int = 1000,
    num_tags: int = 50,
    n_train: int = 4000,
    n_test: int = 500,
) -> FederatedData:
    """Multi-label bag-of-words tag prediction (stand-in for
    stackoverflow_lr; reference multilabel path
    ``fedml_core/trainer/model_trainer.py:57-112``)."""
    rng = np.random.default_rng(cfg.seed)
    W = (rng.random((vocab, num_tags)) < 0.01).astype(np.float32)

    def gen(n):
        x = (rng.random((n, vocab)) < 0.02).astype(np.float32)
        score = x @ W
        y = (score >= np.quantile(score, 0.95, axis=1, keepdims=True)).astype(
            np.float32
        )
        return x, y

    x_tr, y_tr = gen(n_train)
    x_te, y_te = gen(n_test)
    rng2 = np.random.default_rng(cfg.seed + 1)
    perm = rng2.permutation(n_train)
    train_map = {
        i: s for i, s in enumerate(np.array_split(perm, cfg.num_clients))
    }
    test_map = {
        i: s
        for i, s in enumerate(
            np.array_split(np.arange(n_test), cfg.num_clients)
        )
    }
    return FederatedData(
        x_tr, y_tr, x_te, y_te, train_map, test_map, num_tags,
        task="tag_prediction",
    )


# ---------------------------------------------------------------------------
# Real-file parsers
# ---------------------------------------------------------------------------


def _read_idx(path: str) -> np.ndarray:
    """Parse an (optionally gzipped) IDX file (MNIST format)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        zero, dtype_code, ndim = pystruct.unpack(">HBB", f.read(4))
        dims = pystruct.unpack(">" + "I" * ndim, f.read(4 * ndim))
        dt = {8: np.uint8, 9: np.int8, 11: np.int16, 12: np.int32,
              13: np.float32, 14: np.float64}[dtype_code]
        return np.frombuffer(f.read(), dtype=dt).reshape(dims)


def _find(data_dir: str, names: list[str]) -> str | None:
    for n in names:
        p = os.path.join(data_dir, n)
        if os.path.exists(p):
            return p
    return None


def load_mnist_arrays(data_dir: str):
    """MNIST from IDX files (reference loader:
    ``fedml_api/data_preprocessing/MNIST/data_loader.py:93``)."""
    files = {
        "x_tr": ["train-images-idx3-ubyte.gz", "train-images-idx3-ubyte"],
        "y_tr": ["train-labels-idx1-ubyte.gz", "train-labels-idx1-ubyte"],
        "x_te": ["t10k-images-idx3-ubyte.gz", "t10k-images-idx3-ubyte"],
        "y_te": ["t10k-labels-idx1-ubyte.gz", "t10k-labels-idx1-ubyte"],
    }
    paths = {k: _find(data_dir, v) for k, v in files.items()}
    if any(p is None for p in paths.values()):
        raise FileNotFoundError(
            f"MNIST IDX files not found under {data_dir}; fetch with the "
            "reference's data/MNIST/download_and_unzip.sh or use "
            "dataset='fake_mnist'"
        )
    x_tr = _read_idx(paths["x_tr"]).astype(np.float32)[..., None] / 255.0
    x_te = _read_idx(paths["x_te"]).astype(np.float32)[..., None] / 255.0
    return (
        (x_tr - 0.1307) / 0.3081,
        _read_idx(paths["y_tr"]).astype(np.int32),
        (x_te - 0.1307) / 0.3081,
        _read_idx(paths["y_te"]).astype(np.int32),
        10,
    )


def load_cifar_arrays(data_dir: str, name: str):
    """CIFAR-10/100 from the python pickle batches (reference loader:
    ``fedml_api/data_preprocessing/cifar10/data_loader.py:125``)."""
    mean = np.array([0.4914, 0.4822, 0.4465], np.float32)
    std = np.array([0.2470, 0.2435, 0.2616], np.float32)

    def parse(batch_path, label_key):
        with open(batch_path, "rb") as f:
            d = pickle.load(f, encoding="bytes")
        x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        y = np.asarray(d[label_key], np.int32)
        return (x.astype(np.float32) / 255.0 - mean) / std, y

    if name == "cifar10":
        root = _find(data_dir, ["cifar-10-batches-py", "."])
        if root is None or not os.path.exists(
            os.path.join(root, "data_batch_1")
        ):
            raise FileNotFoundError(
                f"cifar-10-batches-py not found under {data_dir}; use "
                "dataset='fake_cifar10' for offline runs"
            )
        parts = [
            parse(os.path.join(root, f"data_batch_{i}"), b"labels")
            for i in range(1, 6)
        ]
        x_tr = np.concatenate([p[0] for p in parts])
        y_tr = np.concatenate([p[1] for p in parts])
        x_te, y_te = parse(os.path.join(root, "test_batch"), b"labels")
        return x_tr, y_tr, x_te, y_te, 10
    root = _find(data_dir, ["cifar-100-python", "."])
    if root is None or not os.path.exists(os.path.join(root, "train")):
        raise FileNotFoundError(
            f"cifar-100-python not found under {data_dir}; use "
            "dataset='fake_cifar100' for offline runs"
        )
    x_tr, y_tr = parse(os.path.join(root, "train"), b"fine_labels")
    x_te, y_te = parse(os.path.join(root, "test"), b"fine_labels")
    return x_tr, y_tr, x_te, y_te, 100


def load_emnist_arrays(data_dir: str, split: str = "balanced"):
    """EMNIST from IDX files (reference
    ``EMNIST/data_loader.py`` via torchvision's ``EMNIST(split='balanced')``
    — the underlying files are gzipped IDX like MNIST). 47 classes for the
    'balanced' split, 62 for 'byclass'."""
    nc = {"balanced": 47, "byclass": 62, "digits": 10, "letters": 26}[split]
    files = {
        k: [f"emnist-{split}-{k2}-idx{d}-ubyte.gz",
            f"emnist-{split}-{k2}-idx{d}-ubyte"]
        for k, (k2, d) in {
            "x_tr": ("train-images", 3), "y_tr": ("train-labels", 1),
            "x_te": ("test-images", 3), "y_te": ("test-labels", 1),
        }.items()
    }
    paths = {k: _find(data_dir, v) for k, v in files.items()}
    if any(p is None for p in paths.values()):
        raise FileNotFoundError(
            f"EMNIST ({split}) IDX files not found under {data_dir}; fetch "
            "with the reference's data scripts or use dataset='fake_emnist'"
        )
    # torchvision stores EMNIST transposed (H/W swapped) vs MNIST; the IDX
    # source files share that orientation — normalize like the reference
    # (_data_transforms_emnist: mean .5, std .5)
    x_tr = _read_idx(paths["x_tr"]).astype(np.float32)[..., None] / 255.0
    x_te = _read_idx(paths["x_te"]).astype(np.float32)[..., None] / 255.0
    return (
        (x_tr - 0.5) / 0.5,
        _read_idx(paths["y_tr"]).astype(np.int32),
        (x_te - 0.5) / 0.5,
        _read_idx(paths["y_te"]).astype(np.int32),
        nc,
    )


def load_image_folder_arrays(data_dir: str, name: str = "cinic10"):
    """CINIC-10-style ImageFolder tree (reference ``cinic10/data_loader.py``
    via ``ImageFolderTruncated``): ``<root>/train/<class>/*.png`` and
    ``<root>/test/<class>/*.png`` (a ``valid/`` split, if present, is folded
    into train like common CINIC practice). Decoded with PIL."""
    from PIL import Image

    mean = np.array([0.47889522, 0.47227842, 0.43047404], np.float32)
    std = np.array([0.24205776, 0.23828046, 0.25874835], np.float32)
    root = _find(data_dir, [name, "CINIC-10", "."])
    if root is None or not os.path.isdir(os.path.join(root, "train")):
        raise FileNotFoundError(
            f"ImageFolder tree (train/<class>/*.png) not found under "
            f"{data_dir}; use dataset='fake_{name}' for offline runs"
        )

    # one canonical class list (from train/) so every split labels by the
    # same name->id map even if a split is missing a class directory
    train_dir = os.path.join(root, "train")
    classes = sorted(
        c
        for c in os.listdir(train_dir)
        if os.path.isdir(os.path.join(train_dir, c))
    )
    class_id = {c: i for i, c in enumerate(classes)}

    def read_split(split):
        d = os.path.join(root, split)
        if not os.path.isdir(d):
            return None, None
        extra = [
            c
            for c in os.listdir(d)
            if os.path.isdir(os.path.join(d, c)) and c not in class_id
        ]
        if extra:
            raise ValueError(
                f"{d} has class dirs {extra} not present in train/"
            )
        xs, ys = [], []
        for c in classes:
            cd = os.path.join(d, c)
            if not os.path.isdir(cd):
                continue
            for fn in sorted(os.listdir(cd)):
                if not fn.lower().endswith((".png", ".jpg", ".jpeg")):
                    continue
                img = np.asarray(
                    Image.open(os.path.join(cd, fn)).convert("RGB"),
                    np.float32,
                ) / 255.0
                xs.append((img - mean) / std)
                ys.append(class_id[c])
        if not xs:
            return None, None
        return np.stack(xs), np.asarray(ys, np.int32)

    x_tr, y_tr = read_split("train")
    x_te, y_te = read_split("test")
    if x_tr is None or x_te is None:
        raise FileNotFoundError(
            f"empty ImageFolder tree under {root}; use "
            f"dataset='fake_{name}'"
        )
    x_va, y_va = read_split("valid")
    if x_va is not None:
        x_tr = np.concatenate([x_tr, x_va])
        y_tr = np.concatenate([y_tr, y_va])
    return x_tr, y_tr, x_te, y_te, max(len(classes), 1)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def load_dataset(cfg: DataConfig) -> FederatedData:
    """Dataset dispatch (reference ``load_data`` tables,
    ``fedml_experiments/distributed/fedavg/main_fedavg.py:133-351`` and
    ``fedml_experiments/standalone/utils/dataset.py:32-168``)."""
    name = cfg.dataset.lower()
    if name == "synthetic_stackoverflow_nwp":
        # checked BEFORE the synthetic_(a)_(b) prefix family below:
        # the EXPLICITLY-REQUESTED seeded StackOverflow-shaped
        # stand-in (same vocab ids and [B, T] int32 contract as the
        # real TFF split) — how CI runs the transformer workload
        # without the 3424-client download. Deliberately a distinct
        # dataset name: a typo'd --data_dir on the real dataset must
        # hard-fail, never silently train on synthetic data
        from fedml_tpu.data.natural import synthetic_stackoverflow_nwp

        return synthetic_stackoverflow_nwp(
            num_clients=cfg.num_clients, seed=cfg.seed
        )
    if name.startswith("synthetic"):
        # "synthetic", "synthetic_1_1", "synthetic_0.5_0.5" ...
        parts = name.split("_")
        a = float(parts[1]) if len(parts) > 1 else 1.0
        b = float(parts[2]) if len(parts) > 2 else 1.0
        return make_synthetic(cfg.num_clients, a, b, seed=cfg.seed)
    if name.startswith("fake_"):
        base = name[len("fake_"):]
        if base in IMAGE_SPECS:
            return make_fake_image_dataset(base, cfg)
        if base in ("shakespeare", "fed_shakespeare"):
            return make_fake_text_dataset(cfg)
        if base in ("stackoverflow_nwp",):
            return make_fake_text_dataset(
                cfg, seq_len=STACKOVERFLOW_SEQ_LEN, vocab=2000
            )
        if base in ("stackoverflow_lr",):
            return make_fake_tag_dataset(cfg)
        if base in ("pascal_voc", "coco_seg", "seg"):
            return make_fake_segmentation_dataset(cfg)
        raise ValueError(f"unknown fake dataset: {name}")
    if name in ("femnist", "fed_emnist", "federated_emnist"):
        from fedml_tpu.data.natural import load_federated_emnist

        return load_federated_emnist(cfg.data_dir)
    if name == "fed_cifar100":
        from fedml_tpu.data.natural import load_fed_cifar100

        return load_fed_cifar100(cfg.data_dir)
    if name.startswith("leaf_"):
        from fedml_tpu.data.natural import load_leaf_json

        base = name[len("leaf_"):]
        if base in ("shakespeare", "fed_shakespeare"):
            from fedml_tpu.data.natural import SHAKESPEARE_VOCAB_SIZE

            return load_leaf_json(
                cfg.data_dir, SHAKESPEARE_VOCAB_SIZE, task="nwp",
                offline_hint="fake_shakespeare", text=True,
            )
        if base == "synthetic":
            # REAL LEAF synthetic(a, b) files; (a, b) parsed from the
            # directory name (synthetic_1_1, synthetic_0.5_0.5, ...)
            # when it follows that convention. A non-conventional name is
            # fine as long as train/mytrain.json exists — (a, b) are only
            # needed to RECONSTRUCT a missing train split.
            from fedml_tpu.data.natural import load_synthetic_leaf

            parts = os.path.basename(
                os.path.normpath(cfg.data_dir)
            ).split("_")
            a = b = None
            if len(parts) == 3 and parts[0] == "synthetic":
                try:
                    a, b = float(parts[1]), float(parts[2])
                except ValueError:
                    a = b = None
            return load_synthetic_leaf(cfg.data_dir, a, b)
        shapes = {"femnist": ((28, 28, 1), 62), "celeba": ((84, 84, 3), 2)}
        if base not in shapes:
            raise ValueError(
                f"unsupported LEAF dataset: {base} (numeric-feature LEAF "
                f"sets supported: {sorted(shapes)})"
            )
        shape, nc2 = shapes[base]
        return load_leaf_json(
            cfg.data_dir, nc2, x_shape=shape,
            offline_hint="fake_femnist" if base == "femnist" else None,
        )
    if name in ("fed_shakespeare", "shakespeare"):
        from fedml_tpu.data.natural import load_fed_shakespeare

        return load_fed_shakespeare(cfg.data_dir)
    if name == "stackoverflow_nwp":
        from fedml_tpu.data.natural import load_stackoverflow_nwp

        return load_stackoverflow_nwp(cfg.data_dir)
    if name == "stackoverflow_lr":
        from fedml_tpu.data.natural import load_stackoverflow_lr

        return load_stackoverflow_lr(cfg.data_dir)
    if name in ("imagenet", "ilsvrc2012"):
        from fedml_tpu.data.largescale import load_imagenet

        return load_imagenet(cfg.data_dir, client_number=cfg.num_clients)
    if name in ("gld23k", "gld160k", "landmarks"):
        from fedml_tpu.data.largescale import load_landmarks

        return load_landmarks(
            cfg.data_dir, split="gld160k" if name == "gld160k" else "gld23k"
        )
    if name == "mnist":
        x_tr, y_tr, x_te, y_te, nc = load_mnist_arrays(cfg.data_dir)
    elif name in ("cifar10", "cifar100"):
        x_tr, y_tr, x_te, y_te, nc = load_cifar_arrays(cfg.data_dir, name)
    elif name == "emnist":
        x_tr, y_tr, x_te, y_te, nc = load_emnist_arrays(cfg.data_dir)
    elif name == "cinic10":
        x_tr, y_tr, x_te, y_te, nc = load_image_folder_arrays(
            cfg.data_dir, name
        )
    else:
        raise ValueError(f"unknown dataset: {cfg.dataset}")
    return build_federated_data(
        x_tr, y_tr, x_te, y_te, nc, cfg.num_clients,
        cfg.partition_method, cfg.partition_alpha, cfg.dataset_r, cfg.seed,
    )
