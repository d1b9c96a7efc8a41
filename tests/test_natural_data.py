"""Natural-split loaders (TFF h5, LEAF json) + backdoor poisoning tests."""

import json
import os

import numpy as np
import pytest

from fedml_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    TrainConfig,
)
from fedml_tpu.data.loaders import load_dataset, make_fake_image_dataset
from fedml_tpu.data.natural import (
    backdoor_success_rate,
    load_federated_emnist,
    load_leaf_json,
    make_backdoor_dataset,
)


def _write_tff_h5(path, n_clients=3, n_per=5, x_field="pixels",
                  y_field="label"):
    import h5py

    rng = np.random.default_rng(0)
    with h5py.File(path, "w") as f:
        ex = f.create_group("examples")
        for c in range(n_clients):
            g = ex.create_group(f"client_{c}")
            g.create_dataset(
                x_field, data=rng.random((n_per, 28, 28), np.float32)
            )
            g.create_dataset(
                y_field, data=rng.integers(0, 62, n_per).astype(np.int32)
            )


def test_load_federated_emnist_h5(tmp_path):
    _write_tff_h5(tmp_path / "fed_emnist_train.h5")
    _write_tff_h5(tmp_path / "fed_emnist_test.h5")
    data = load_federated_emnist(str(tmp_path))
    assert data.num_clients == 3
    assert data.x_train.shape == (15, 28, 28, 1)
    assert all(len(v) == 5 for v in data.train_idx_map.values())


def test_missing_file_raises_with_fake_hint(tmp_path):
    with pytest.raises(FileNotFoundError, match="fake_femnist"):
        load_federated_emnist(str(tmp_path / "nope"))


def test_load_leaf_json(tmp_path):
    rng = np.random.default_rng(0)
    for split in ("train", "test"):
        os.makedirs(tmp_path / split)
        blob = {
            "users": ["u0", "u1"],
            "user_data": {
                u: {
                    "x": rng.random((4, 784)).tolist(),
                    "y": rng.integers(0, 62, 4).tolist(),
                }
                for u in ("u0", "u1")
            },
        }
        with open(tmp_path / split / "data.json", "w") as f:
            json.dump(blob, f)
    data = load_leaf_json(str(tmp_path), 62, x_shape=(28, 28, 1))
    assert data.num_clients == 2
    assert data.x_train.shape == (8, 28, 28, 1)


def test_backdoor_and_robust_aggregation():
    """Poisoned FedAvg: plain mean lets the backdoor in; coordinate-median
    suppresses it (the fedavg_robust defense)."""
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.models import create_model

    def cfg_with(robust_method):
        return ExperimentConfig(
            data=DataConfig(dataset="fake_mnist", num_clients=6,
                            partition_method="homo", batch_size=16, seed=0),
            model=ModelConfig(name="lr", num_classes=10,
                              input_shape=(28, 28, 1)),
            train=TrainConfig(lr=0.1, epochs=2),
            fed=FedConfig(num_rounds=4, clients_per_round=6,
                          robust_method=robust_method),
            seed=0,
        )

    clean = make_fake_image_dataset(
        "mnist", cfg_with("mean").data, n_train=600, n_test=120
    )
    poisoned, trig_x, trig_y = make_backdoor_dataset(
        clean, target_label=0, poison_fraction=0.9,
        attacker_clients=(0, 1), seed=0,
    )
    results = {}
    for method in ("mean", "median"):
        cfg = cfg_with(method)
        sim = FedAvgSim(create_model(cfg.model), poisoned, cfg)
        state = sim.init()
        for _ in range(4):
            state, _ = sim.run_round(state)
        results[method] = backdoor_success_rate(
            sim.model, state.variables, trig_x[:64], trig_y[:64]
        )
    # median should not be MORE backdoored than plain mean
    assert results["median"] <= results["mean"] + 0.05, results


# ---------------------------------------------------------------------------
# Real-file text loaders (fed_shakespeare, stackoverflow nwp/lr)
# ---------------------------------------------------------------------------


def _write_text_h5(path, field_rows: dict):
    """field_rows: {client_id: {field: [str, ...]}}"""
    import h5py

    with h5py.File(path, "w") as f:
        ex = f.create_group("examples")
        for cid, fields in field_rows.items():
            g = ex.create_group(cid)
            for field, rows in fields.items():
                g.create_dataset(
                    field, data=np.array([r.encode("utf8") for r in rows])
                )


def test_fed_shakespeare_h5_roundtrip(tmp_path):
    from fedml_tpu.data.natural import (
        SHAKESPEARE_CHARS,
        SHAKESPEARE_VOCAB_SIZE,
        load_fed_shakespeare,
        shakespeare_to_sequences,
    )

    snippet = "To be, or not to be"
    _write_text_h5(
        tmp_path / "shakespeare_train.h5",
        {"c0": {"snippets": [snippet]}, "c1": {"snippets": ["ay\nthere"]}},
    )
    _write_text_h5(
        tmp_path / "shakespeare_test.h5",
        {"c0": {"snippets": [snippet]}, "c1": {"snippets": ["the rub"]}},
    )
    data = load_fed_shakespeare(str(tmp_path))
    assert data.task == "nwp"
    assert data.num_classes == SHAKESPEARE_VOCAB_SIZE == 90
    assert data.num_clients == 2
    assert data.x_train.shape[1] == 80
    # tokenization parity with the reference's preprocess():
    # [bos] + char ids + [eos], zero-padded to 81
    seqs = shakespeare_to_sequences([snippet])
    assert seqs.shape == (1, 81)
    bos = len(SHAKESPEARE_CHARS) + 1
    eos = len(SHAKESPEARE_CHARS) + 2
    assert seqs[0, 0] == bos
    char_id = {c: i + 1 for i, c in enumerate(SHAKESPEARE_CHARS)}
    assert seqs[0, 1] == char_id["T"]
    assert seqs[0, len(snippet) + 1] == eos
    assert (seqs[0, len(snippet) + 2 :] == 0).all()  # pad
    # y is x shifted by one (next-char targets)
    np.testing.assert_array_equal(data.x_train[0, 1:], data.y_train[0, :-1])


def test_stackoverflow_nwp_h5_roundtrip(tmp_path):
    from fedml_tpu.data.natural import (
        load_stackoverflow_nwp,
        stackoverflow_to_sequences,
    )

    vocab = [f"w{i}" for i in range(30)]
    (tmp_path / "stackoverflow.word_count").write_text(
        "".join(f"{w} {1000 - i}\n" for i, w in enumerate(vocab))
    )
    _write_text_h5(
        tmp_path / "stackoverflow_train.h5",
        {"u0": {"tokens": ["w0 w1 w2", "w3 unknownword"]},
         "u1": {"tokens": ["w4 w5"]}},
    )
    _write_text_h5(
        tmp_path / "stackoverflow_test.h5",
        {"u0": {"tokens": ["w1 w2"]}, "u1": {"tokens": ["w0"]}},
    )
    data = load_stackoverflow_nwp(str(tmp_path), vocab_size=30, seq_len=5)
    assert data.task == "nwp"
    assert data.num_classes == 34  # 30 words + pad + bos + eos + oov
    assert data.num_clients == 2
    assert data.x_train.shape == (3, 5)
    word_dict = {w: i for i, w in enumerate(vocab)}
    seqs = stackoverflow_to_sequences(["w0 w1 w2"], word_dict, seq_len=5)
    bos, eos, oov = 31, 32, 33
    # [bos, w0, w1, w2, eos, pad]: short sentence gets eos then pad
    np.testing.assert_array_equal(seqs[0], [bos, 1, 2, 3, eos, 0])
    # oov words map to the oov bucket
    seqs = stackoverflow_to_sequences(["zzz w0"], word_dict, seq_len=5)
    assert seqs[0, 1] == oov


def test_stackoverflow_lr_h5_roundtrip(tmp_path):
    from fedml_tpu.data.natural import load_stackoverflow_lr

    vocab = ["alpha", "beta", "gamma"]
    (tmp_path / "stackoverflow.word_count").write_text(
        "alpha 10\nbeta 9\ngamma 8\n"
    )
    (tmp_path / "stackoverflow.tag_count").write_text(
        json.dumps({"python": 100, "jax": 50, "tpu": 25})
    )
    _write_text_h5(
        tmp_path / "stackoverflow_train.h5",
        {"u0": {"tokens": ["alpha beta", "gamma gamma oovword"],
                "tags": ["python|jax", "tpu"]},
         "u1": {"tokens": ["alpha"], "tags": ["python"]}},
    )
    _write_text_h5(
        tmp_path / "stackoverflow_test.h5",
        {"u0": {"tokens": ["beta"], "tags": ["jax"]},
         "u1": {"tokens": ["gamma"], "tags": ["tpu"]}},
    )
    data = load_stackoverflow_lr(str(tmp_path), vocab_size=3, tag_size=3)
    assert data.task == "tag_prediction"
    assert data.num_classes == 3
    assert data.x_train.shape == (3, 3)
    # "alpha beta" -> mean one-hot = [.5, .5, 0]
    np.testing.assert_allclose(data.x_train[0], [0.5, 0.5, 0.0])
    # "gamma gamma oovword" -> [0, 0, 2/3] (oov counts in the denominator)
    np.testing.assert_allclose(data.x_train[1], [0, 0, 2 / 3], atol=1e-6)
    # tags "python|jax" -> [1, 1, 0]
    np.testing.assert_array_equal(data.y_train[0], [1, 1, 0])


def test_emnist_idx_roundtrip(tmp_path):
    import gzip
    import struct

    from fedml_tpu.data.loaders import load_emnist_arrays

    rng = np.random.default_rng(0)

    def write_idx(path, arr):
        arr = np.ascontiguousarray(arr)
        header = struct.pack(
            ">HBB", 0, 8, arr.ndim
        ) + struct.pack(">" + "I" * arr.ndim, *arr.shape)
        with gzip.open(path, "wb") as f:
            f.write(header + arr.astype(np.uint8).tobytes())

    write_idx(tmp_path / "emnist-balanced-train-images-idx3-ubyte.gz",
              rng.integers(0, 255, (20, 28, 28)))
    write_idx(tmp_path / "emnist-balanced-train-labels-idx1-ubyte.gz",
              rng.integers(0, 47, (20,)))
    write_idx(tmp_path / "emnist-balanced-test-images-idx3-ubyte.gz",
              rng.integers(0, 255, (8, 28, 28)))
    write_idx(tmp_path / "emnist-balanced-test-labels-idx1-ubyte.gz",
              rng.integers(0, 47, (8,)))
    x_tr, y_tr, x_te, y_te, nc = load_emnist_arrays(str(tmp_path))
    assert x_tr.shape == (20, 28, 28, 1) and nc == 47
    assert x_te.shape == (8, 28, 28, 1)
    assert np.abs(x_tr).max() <= 1.0 + 1e-6  # (x/255 - .5)/.5 in [-1, 1]


def test_cinic10_image_folder_roundtrip(tmp_path):
    from PIL import Image

    from fedml_tpu.data.loaders import load_image_folder_arrays

    rng = np.random.default_rng(0)
    classes = ["airplane", "cat"]
    for split, n in (("train", 3), ("valid", 2), ("test", 2)):
        for c in classes:
            d = tmp_path / "cinic10" / split / c
            d.mkdir(parents=True)
            for i in range(n):
                Image.fromarray(
                    rng.integers(0, 255, (32, 32, 3)).astype(np.uint8)
                ).save(d / f"img{i}.png")
    x_tr, y_tr, x_te, y_te, nc = load_image_folder_arrays(
        str(tmp_path), "cinic10"
    )
    assert nc == 2
    assert x_tr.shape == (10, 32, 32, 3)  # train(6) + valid(4) folded in
    assert x_te.shape == (4, 32, 32, 3)
    assert set(np.unique(y_tr)) == {0, 1}


def test_real_text_datasets_via_dispatch(tmp_path):
    """load_dataset() routes the real names to the h5 readers."""
    from fedml_tpu.data.loaders import load_dataset

    _write_text_h5(
        tmp_path / "shakespeare_train.h5",
        {"c0": {"snippets": ["hello world"]}},
    )
    _write_text_h5(
        tmp_path / "shakespeare_test.h5",
        {"c0": {"snippets": ["bye"]}},
    )
    data = load_dataset(
        DataConfig(dataset="fed_shakespeare", data_dir=str(tmp_path))
    )
    assert data.task == "nwp" and data.num_clients == 1


def _make_image_tree(tmp_path, classes, per_split, size=8, seed=0):
    """ImageFolder tree train/<class>/*.jpg + val/<class>/*.jpg."""
    from PIL import Image

    rng = np.random.default_rng(seed)
    for split, n in per_split.items():
        for c in classes:
            d = tmp_path / split / c
            d.mkdir(parents=True)
            for i in range(n):
                Image.fromarray(
                    rng.integers(0, 255, (size, size, 3)).astype(np.uint8)
                ).save(d / f"{c}_{i}.jpg")


def test_imagenet_by_class_partition(tmp_path):
    """ImageNet federated partition: classes dealt to clients in sorted
    order (reference load_partition_data_ImageNet:235-243)."""
    from fedml_tpu.data.largescale import load_imagenet

    _make_image_tree(
        tmp_path, ("n01440764", "n01443537", "n01484850", "n01491361"),
        {"train": 3, "val": 1},
    )
    data = load_imagenet(str(tmp_path), client_number=2, image_size=8)
    assert data.num_clients == 2 and data.num_classes == 4
    # client 0 owns classes {0,1}, client 1 owns {2,3}
    assert set(data.y_train[data.train_idx_map[0]]) == {0, 1}
    assert set(data.y_train[data.train_idx_map[1]]) == {2, 3}
    assert data.x_train.shape == (12, 8, 8, 3)
    # client_range decodes only that shard's clients
    part = load_imagenet(str(tmp_path), client_number=2, image_size=8,
                         client_range=(1, 2))
    assert len(part.train_idx_map[0]) == 0
    assert len(part.train_idx_map[1]) == 6


def test_landmarks_user_split(tmp_path):
    """gld23k-style mapping csv -> natural per-user partition (reference
    get_mapping_per_user)."""
    from PIL import Image

    from fedml_tpu.data.largescale import load_landmarks

    rng = np.random.default_rng(0)
    (tmp_path / "data_user_dict").mkdir()
    (tmp_path / "images").mkdir()
    rows = ["user_id,image_id,class"]
    for u, imgs in ((0, ["a", "b"]), (7, ["c"])):
        for im in imgs:
            rows.append(f"{u},{im},{u % 2}")
            Image.fromarray(
                rng.integers(0, 255, (8, 8, 3)).astype(np.uint8)
            ).save(tmp_path / "images" / f"{im}.jpg")
    (tmp_path / "data_user_dict" / "gld23k_user_dict_train.csv").write_text(
        "\n".join(rows) + "\n"
    )
    (tmp_path / "data_user_dict" / "gld23k_user_dict_test.csv").write_text(
        "user_id,image_id,class\n0,a,0\n"
    )
    data = load_landmarks(str(tmp_path), image_size=8)
    assert data.num_clients == 2
    assert len(data.train_idx_map[0]) == 2  # user "0"
    assert len(data.train_idx_map[1]) == 1  # user "7"
    assert data.x_test.shape == (1, 8, 8, 3)


def test_edge_case_backdoor_suite(tmp_path):
    """Edge-case pool attacks (southwest/ARDIS analog): pool mixing per
    attack_case, real-pickle loading, and targeted-task evaluation."""
    import pickle

    from fedml_tpu.data.natural import (
        EdgeCasePool,
        load_southwest_pool,
        make_edge_case_backdoor,
        make_procedural_edge_pool,
    )

    data = make_fake_image_dataset(
        "cifar10",
        DataConfig(dataset="fake_cifar10", num_clients=4, seed=0),
        n_train=400, n_test=80,
    )
    pool = make_procedural_edge_pool(data, n_train=50, n_test=20,
                                     target_label=9)
    for case in ("edge-case", "almost-edge-case", "normal-case"):
        poisoned, tx, ty = make_edge_case_backdoor(
            data, pool, attacker_clients=(1,), attack_case=case,
            poison_fraction=0.5, seed=0,
        )
        idx = np.asarray(data.train_idx_map[1])
        flipped = (poisoned.y_train[idx] == 9).sum()
        assert flipped >= len(idx) // 2 - 1
        assert tx.shape == (20, 32, 32, 3)
        assert (ty == 9).all()
        if case == "normal-case":  # inputs unchanged, labels flipped
            np.testing.assert_array_equal(poisoned.x_train[idx],
                                          data.x_train[idx])
        else:  # inputs replaced by pool examples
            assert not np.allclose(poisoned.x_train[idx], data.x_train[idx])
        # non-attacker clients untouched
        idx0 = np.asarray(data.train_idx_map[0])
        np.testing.assert_array_equal(poisoned.x_train[idx0],
                                      data.x_train[idx0])

    # real southwest pickle format round-trip
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, (30, 32, 32, 3)).astype(np.uint8)
    for name, arr in (("southwest_images_new_train.pkl", imgs),
                      ("southwest_images_new_test.pkl", imgs[:10])):
        with open(tmp_path / name, "wb") as f:
            pickle.dump(arr, f)
    sw = load_southwest_pool(str(tmp_path))
    assert sw.x_train.shape == (30, 32, 32, 3)
    assert sw.x_train.max() <= 1.0 and sw.target_label == 9


def test_nus_wide_two_party_loader(tmp_path):
    """NUS-WIDE layout round-trip: label txts + normalized feature dats +
    tags, exactly-one-hot filtering, party column splits."""
    from fedml_tpu.data.vertical import load_nus_wide_two_party

    rng = np.random.default_rng(0)
    labels = ["buildings", "grass"]
    n = 20
    (tmp_path / "Groundtruth" / "TrainTestLabels").mkdir(parents=True)
    (tmp_path / "Low_Level_Features").mkdir()
    (tmp_path / "NUS_WID_Tags").mkdir()
    for dtype, m in (("Train", n), ("Test", 8)):
        l0 = rng.integers(0, 2, m)
        l1 = 1 - l0  # exactly one active for most rows
        l1[:2] = l0[:2]  # a few invalid rows (0 or 2 active)
        np.savetxt(tmp_path / "Groundtruth" / "TrainTestLabels"
                   / f"Labels_buildings_{dtype}.txt", l0, fmt="%d")
        np.savetxt(tmp_path / "Groundtruth" / "TrainTestLabels"
                   / f"Labels_grass_{dtype}.txt", l1, fmt="%d")
        np.savetxt(tmp_path / "Low_Level_Features"
                   / f"{dtype}_Normalized_CH.dat",
                   rng.random((m, 3)), fmt="%.4f")
        np.savetxt(tmp_path / "Low_Level_Features"
                   / f"{dtype}_Normalized_EDH.dat",
                   rng.random((m, 2)), fmt="%.4f")
        np.savetxt(tmp_path / "NUS_WID_Tags" / f"{dtype}_Tags1k.dat",
                   rng.integers(0, 2, (m, 5)), fmt="%d", delimiter="\t")
    out = load_nus_wide_two_party(str(tmp_path), selected_labels=labels)
    x, y = out["train"]
    assert x.shape[1] == 3 + 2 + 5
    assert out["splits"] == [(0, 5), (5, 10)]
    assert set(np.unique(y)) <= {0, 1}
    # invalid rows (not exactly one concept) were dropped
    assert x.shape[0] <= n - 1


def test_lending_club_two_party_loader(tmp_path):
    from fedml_tpu.data.vertical import (
        PARTY_A_FEATS,
        PARTY_B_FEATS,
        load_lending_club_two_party,
    )

    rows = [
        ",".join(["grade", "emp_length", "home_ownership", "annual_inc",
                  "verification_status", "loan_amnt", "term",
                  "initial_list_status", "purpose", "application_type",
                  "disbursement_method", "int_rate", "installment", "dti",
                  "delinq_2yrs", "open_acc", "pub_rec", "revol_bal",
                  "revol_util", "total_acc", "loan_status"])
    ]
    import csv as _csv
    import io

    buf = io.StringIO()
    w = _csv.writer(buf)
    w.writerow(rows[0].split(","))
    statuses = ["Fully Paid", "Charged Off", "Current", "Default"] * 5
    for i, st in enumerate(statuses):
        w.writerow(["B", "5 years", "RENT", 50000 + i, "Verified",
                    10000, " 36 months", "w", "credit_card", "Individual",
                    "Cash", f"{10 + i * 0.1:.1f}%", 300, 15.0, 0, 8, 0,
                    12000, "45.3", 20, st])
    (tmp_path / "loan.csv").write_text(buf.getvalue())
    out = load_lending_club_two_party(str(tmp_path / "loan.csv"))
    x_tr, y_tr = out["train"]
    x_te, y_te = out["test"]
    assert x_tr.shape[1] == len(PARTY_A_FEATS) + len(PARTY_B_FEATS)
    assert out["splits"][0] == (0, len(PARTY_A_FEATS))
    # bad-loan labeling: Charged Off / Default -> 1
    all_y = np.concatenate([y_tr, y_te])
    assert all_y.sum() == 10  # half the rows


def test_vfl_sim_on_loaded_vertical_data(tmp_path):
    """The loaders' output feeds VFLSim end-to-end and learns."""
    from fedml_tpu.algorithms.split import VFLSim
    from fedml_tpu.models.gkt import VFLDenseModel, VFLLocalModel

    rng = np.random.default_rng(0)
    n, da, db = 400, 6, 4
    x = rng.normal(size=(n, da + db)).astype(np.float32)
    w = rng.normal(size=(da + db,))
    y = (x @ w > 0).astype(np.int64)
    data = {"train": (x[:300], y[:300]), "test": (x[300:], y[300:]),
            "splits": [(0, da), (da, da + db)]}
    cfg = ExperimentConfig(
        data=DataConfig(dataset="vfl", batch_size=32),
        model=ModelConfig(name="lr", num_classes=1, input_shape=(da + db,)),
        train=TrainConfig(lr=0.1, epochs=1),
        fed=FedConfig(num_rounds=30, clients_per_round=2, eval_every=30),
        seed=0,
    )
    parties = [
        (VFLLocalModel(out_dim=8), VFLDenseModel())
        for _ in data["splits"]
    ]
    sim = VFLSim(parties, data["splits"], *data["train"], *data["test"],
                 cfg)
    state = sim.init()
    for _ in range(30):
        state, _ = sim.run_epoch(state)
    m = sim.evaluate(state)
    assert m["test_acc"] > 0.8, m


def test_leaf_text_shakespeare_json(tmp_path):
    """LEAF text format (shakespeare): 80-char contexts + next-char labels
    tokenize with the shared char vocabulary into shifted LM targets."""
    from fedml_tpu.data.natural import SHAKESPEARE_CHARS

    ctx = "to be or not to be that is the question "
    blob = {
        "users": ["u0", "u1"],
        "user_data": {
            "u0": {"x": [ctx, ctx[1:] + "x"], "y": ["t", "h"]},
            "u1": {"x": [ctx], "y": ["q"]},
        },
    }
    for split in ("train", "test"):
        d = tmp_path / split
        d.mkdir()
        (d / "data.json").write_text(json.dumps(blob))
    # test split is missing u1 (LEAF --by-user): its slice must be an
    # empty [0, L] int32, not a 1-D float placeholder
    test_blob = {"users": ["u0"],
                 "user_data": {"u0": {"x": [ctx], "y": ["t"]}}}
    (tmp_path / "test" / "data.json").write_text(json.dumps(test_blob))
    data = load_dataset(
        DataConfig(dataset="leaf_shakespeare", data_dir=str(tmp_path))
    )
    assert data.task == "nwp" and data.num_clients == 2
    assert data.x_test.dtype == np.int32
    assert len(data.test_idx_map[1]) == 0  # u1 absent from test
    assert data.x_train.shape == (3, len(ctx))
    char_id = {c: i + 1 for i, c in enumerate(SHAKESPEARE_CHARS)}
    # shifted: y[:, :-1] == x[:, 1:], last y col is the LEAF next char
    np.testing.assert_array_equal(data.y_train[0, :-1], data.x_train[0, 1:])
    assert data.y_train[0, -1] == char_id["t"]


def test_imagenet_remainder_dealing_and_test_maps(tmp_path):
    """classes % clients != 0: remainder classes deal one each to the
    first clients (no divisibility assert), and the vectorized per-client
    test maps give each client exactly its own classes' val images."""
    from fedml_tpu.data.largescale import load_imagenet

    _make_image_tree(tmp_path, ["c%02d" % i for i in range(5)],
                     {"train": 2, "val": 2}, seed=1)
    data = load_imagenet(str(tmp_path), client_number=2, image_size=8)
    # 5 classes over 2 clients: client 0 gets {0,1,2}, client 1 {3,4}
    assert set(data.y_train[data.train_idx_map[0]]) == {0, 1, 2}
    assert set(data.y_train[data.train_idx_map[1]]) == {3, 4}
    # per-client test maps cover the val set disjointly, own classes only
    te0 = set(map(int, data.test_idx_map[0]))
    te1 = set(map(int, data.test_idx_map[1]))
    assert te0.isdisjoint(te1)
    assert len(te0) + len(te1) == len(data.y_test)
    assert set(data.y_test[sorted(te0)]) == {0, 1, 2}
    assert set(data.y_test[sorted(te1)]) == {3, 4}
    # too many clients for the class count fails loudly
    with pytest.raises(ValueError, match="dealt"):
        load_imagenet(str(tmp_path), client_number=6, image_size=8)


REFERENCE_SYNTH = "/root/reference/data/synthetic_1_1"


@pytest.mark.skipif(
    not os.path.exists(os.path.join(REFERENCE_SYNTH, "test", "mytest.json")),
    reason="reference LEAF synthetic files not present",
)
@pytest.mark.parametrize("dirname,a,b", [
    ("synthetic_0_0", 0.0, 0.0),
    ("synthetic_0.5_0.5", 0.5, 0.5),
    ("synthetic_1_1", 1.0, 1.0),
])
def test_real_leaf_synthetic_reconstruction(dirname, a, b):
    """The REAL in-tree LEAF synthetic files load end-to-end for ALL
    three (alpha, beta) settings the reference ships: the held-out test
    split is the shipped ``test/mytest.json`` verbatim, and the
    reconstructed train split is its exact complement in the seeded
    FedProx generation (reference ``data/synthetic_*/
    generate_synthetic.py``; benchmark row ``benchmark/README.md:14``).
    Measured on the real files (FedAvg+LR, reference hyperparameters):
    best test acc within 200 rounds = 80.2 / 80.0 / 92.1 % for
    (0,0) / (0.5,0.5) / (1,1) — all above the reference's >60 bar."""
    from fedml_tpu.data.natural import load_synthetic_leaf

    ref_dir = os.path.join(os.path.dirname(REFERENCE_SYNTH), dirname)
    if not os.path.exists(os.path.join(ref_dir, "test", "mytest.json")):
        pytest.skip(f"{dirname} files not present in this checkout")
    data = load_synthetic_leaf(ref_dir, a, b)
    assert data.num_clients == 30
    st = data.stats()
    # the shipped test files carry 2248 samples over 30 users; the full
    # seeded generation has sum(lognormal sizes) = 22349
    assert st["test_num"] == 2248
    assert st["train_num"] == 22349 - 2248
    # per-user train+test == the seeded per-user generation size
    np.random.seed(0)
    sizes = np.random.lognormal(4, 2, 30).astype(int) + 50
    for i in range(30):
        assert (
            len(data.train_idx_map[i]) + len(data.test_idx_map[i])
            == sizes[i]
        )
    # test arrays are the json rows verbatim (float32 cast only)
    with open(os.path.join(ref_dir, "test", "mytest.json")) as f:
        blob = json.load(f)
    u0 = blob["users"][0]
    np.testing.assert_array_equal(
        data.x_test[data.test_idx_map[0]],
        np.asarray(blob["user_data"][u0]["x"], np.float32),
    )
    np.testing.assert_array_equal(
        data.y_test[data.test_idx_map[0]],
        np.asarray(blob["user_data"][u0]["y"], np.int32),
    )
    # no train/test leakage: train rows disjoint from test rows per user
    te_keys = {r.tobytes() for r in data.x_test}
    assert not any(
        data.x_train[j].tobytes() in te_keys
        for j in data.train_idx_map[0][:50]
    )
    # dispatch path: dataset="leaf_synthetic" parses (a, b) from data_dir
    d2 = load_dataset(
        DataConfig(dataset="leaf_synthetic", data_dir=ref_dir)
    )
    assert d2.stats() == st


@pytest.mark.skipif(
    not os.path.exists(os.path.join(REFERENCE_SYNTH, "test", "mytest.json")),
    reason="reference LEAF synthetic files not present",
)
def test_real_leaf_synthetic_fedavg_learns():
    """FedAvg + LR on the REAL synthetic(1,1) data with the reference
    benchmark hyperparameters (30 clients, 10/round, batch 10, SGD lr
    .01) climbs well past chance within 30 rounds — the short-horizon
    version of the >60-acc-at-200-rounds row of BASELINE.md."""
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.models import create_model

    cfg = ExperimentConfig(
        data=DataConfig(dataset="leaf_synthetic",
                        data_dir=REFERENCE_SYNTH,
                        num_clients=30, batch_size=10, seed=0),
        model=ModelConfig(name="lr", num_classes=10, input_shape=(60,)),
        train=TrainConfig(lr=0.01, epochs=1),
        fed=FedConfig(num_rounds=30, clients_per_round=10,
                      eval_every=10**9),
        seed=0,
    )
    data = load_dataset(cfg.data)
    sim = FedAvgSim(create_model(cfg.model), data, cfg)
    state = sim.init()
    for _ in range(30):
        state, _ = sim.run_round(state)
    assert sim.evaluate_global(state)["acc"] > 0.6
