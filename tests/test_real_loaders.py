"""Real-file ingestion coverage: fabricated on-disk fixtures in the exact
formats the reference consumes (MNIST idx, CIFAR-10 pickle batches,
Tiny-ImageNet class folders, LOAN per-state CSVs — image_helper.py:173-250,
loan_helper.py:111-132) run through loader → partition → device data → one
FL round. Zero-egress: the files are fabricated, the formats are real."""
import gzip
import pickle
import struct

import numpy as np
import pytest

from dba_mod_tpu.config import Params
from dba_mod_tpu.data import datasets as ds
from dba_mod_tpu.fl.experiment import Experiment


def _round_cfg(**kw):
    base = dict(lr=0.1, eta=0.8, aggregation_methods="mean",
                internal_epochs=1, is_poison=False, momentum=0.9,
                decay=0.0005, sampling_dirichlet=False, local_eval=False,
                random_seed=1, synthetic_data=False, epochs=1)
    base.update(kw)
    return Params.from_dict(base)


# ---------------------------------------------------------------------- MNIST
def _write_idx_images(path, arr):
    with open(path, "wb") as f:
        f.write(struct.pack(">I", 0x00000803))
        f.write(struct.pack(">III", *arr.shape))
        f.write(arr.tobytes())


def _write_idx_labels(path, arr):
    with open(path, "wb") as f:
        f.write(struct.pack(">I", 0x00000801))
        f.write(struct.pack(">I", arr.shape[0]))
        f.write(arr.tobytes())


def _fake_mnist(root, n_train=600, n_test=256):
    rng = np.random.RandomState(0)
    tr_x = rng.randint(0, 256, (n_train, 28, 28), dtype=np.uint8)
    tr_y = rng.randint(0, 10, n_train).astype(np.uint8)
    te_x = rng.randint(0, 256, (n_test, 28, 28), dtype=np.uint8)
    te_y = rng.randint(0, 10, n_test).astype(np.uint8)
    d = root / "MNIST" / "raw"
    d.mkdir(parents=True)
    _write_idx_images(d / "train-images-idx3-ubyte", tr_x)
    _write_idx_labels(d / "train-labels-idx1-ubyte", tr_y)
    # gzip variant exercises the .gz opener branch
    raw = (struct.pack(">I", 0x00000803) + struct.pack(">III", *te_x.shape)
           + te_x.tobytes())
    with gzip.open(d / "t10k-images-idx3-ubyte.gz", "wb") as f:
        f.write(raw)
    _write_idx_labels(d / "t10k-labels-idx1-ubyte", te_y)
    return tr_x, tr_y, te_x, te_y


def test_mnist_idx_loader_and_round(tmp_path):
    tr_x, tr_y, te_x, te_y = _fake_mnist(tmp_path)
    data = ds.load_mnist(str(tmp_path))
    assert data is not None and not data.synthetic
    np.testing.assert_array_equal(data.train_images[..., 0], tr_x)
    np.testing.assert_array_equal(data.train_labels, tr_y)
    np.testing.assert_array_equal(data.test_images[..., 0], te_x)  # .gz path
    assert data.num_classes == 10

    e = Experiment(_round_cfg(type="mnist", batch_size=16, no_models=4,
                              number_of_total_participants=10,
                              data_dir=str(tmp_path)), save_results=False)
    assert not e.image_data.synthetic
    r = e.run_round(1)
    assert np.isfinite(r["global_acc"])


# --------------------------------------------------------------------- CIFAR10
def _fake_cifar(root, n_train=144, n_test=64):
    rng = np.random.RandomState(1)
    d = root / "cifar-10-batches-py"
    d.mkdir(parents=True)
    per = n_train // 5
    all_imgs, all_labels = [], []
    for i in range(1, 6):
        n = per if i < 5 else n_train - 4 * per
        imgs = rng.randint(0, 256, (n, 3, 32, 32), dtype=np.uint8)
        labels = rng.randint(0, 10, n).astype(int).tolist()
        with open(d / f"data_batch_{i}", "wb") as f:
            pickle.dump({b"data": imgs.reshape(n, -1), b"labels": labels}, f)
        all_imgs.append(imgs), all_labels.extend(labels)
    te = rng.randint(0, 256, (n_test, 3, 32, 32), dtype=np.uint8)
    te_l = rng.randint(0, 10, n_test).astype(int).tolist()
    with open(d / "test_batch", "wb") as f:
        pickle.dump({b"data": te.reshape(n_test, -1), b"labels": te_l}, f)
    return np.concatenate(all_imgs), np.array(all_labels), te, np.array(te_l)


def test_cifar_pickle_loader_and_round(tmp_path, narrow_resnets):
    tr, tr_y, te, te_y = _fake_cifar(tmp_path)
    data = ds.load_cifar10(str(tmp_path))
    assert data is not None
    # channel order: pickle rows are CHW planes → loader must emit NHWC
    np.testing.assert_array_equal(data.train_images,
                                  tr.transpose(0, 2, 3, 1))
    np.testing.assert_array_equal(data.train_labels, tr_y)
    np.testing.assert_array_equal(data.test_images,
                                  te.transpose(0, 2, 3, 1))

    e = Experiment(_round_cfg(type="cifar", batch_size=8, no_models=3,
                              number_of_total_participants=6,
                              data_dir=str(tmp_path)), save_results=False)
    assert not e.image_data.synthetic
    r = e.run_round(1)
    assert np.isfinite(r["global_acc"])


# -------------------------------------------------------------- Tiny-ImageNet
def test_tiny_folder_loader_and_round(tmp_path, narrow_resnets):
    PIL = pytest.importorskip("PIL.Image")
    rng = np.random.RandomState(2)
    root = tmp_path / "tiny-imagenet-200"
    wnids = ["n01443537", "n01629819"]
    for split, per in (("train", 16), ("val", 8)):
        for w in wnids:
            d = root / split / w / ("images" if split == "train" else "")
            d.mkdir(parents=True, exist_ok=True)
            for j in range(per):
                img = rng.randint(0, 256, (64, 64, 3), dtype=np.uint8)
                PIL.fromarray(img).save(d / f"{w}_{j}.JPEG", quality=95)
    data = ds.load_tiny_imagenet(str(tmp_path))
    assert data is not None
    assert data.train_images.shape == (32, 64, 64, 3)
    assert data.test_images.shape == (16, 64, 64, 3)
    assert set(data.train_labels) == {0, 1} and data.num_classes == 200

    e = Experiment(_round_cfg(type="tiny-imagenet-200", batch_size=4,
                              no_models=2, number_of_total_participants=4,
                              lr=0.05, data_dir=str(tmp_path)),
                   save_results=False)
    assert not e.image_data.synthetic
    r = e.run_round(1)
    assert np.isfinite(r["global_acc"])


# ------------------------------------------------------------------------ LOAN
def test_loan_csv_loader_and_round(tmp_path):
    pd = pytest.importorskip("pandas")
    pytest.importorskip("sklearn")
    rng = np.random.RandomState(3)
    d = tmp_path / "loan"
    d.mkdir()
    # LoanNet's input layer is the reference's 91-feature schema
    feats = ds._LOAN_TRIGGER_FEATURES + [
        f"feat_{i}" for i in range(91 - len(ds._LOAN_TRIGGER_FEATURES))]
    rows = {}
    for state, n in (("AK", 40), ("AL", 52), ("AR", 36), ("AZ", 44)):
        df = pd.DataFrame(rng.randn(n, len(feats)).astype(np.float32),
                          columns=feats)
        df["loan_status"] = rng.randint(0, 9, n)
        df.to_csv(d / f"loan_{state}.csv", index=False)
        rows[state] = n
    data = ds.load_loan_csvs(str(tmp_path))
    assert data is not None
    assert data.state_names == ["AK", "AL", "AR", "AZ"]
    assert data.feature_names == feats
    for i, s in enumerate(data.state_names):
        # sklearn random_state=42 80/20 split parity (loan_helper.py:172)
        assert len(data.train_y[i]) == rows[s] - int(np.ceil(0.2 * rows[s]))
        assert len(data.test_y[i]) == int(np.ceil(0.2 * rows[s]))

    e = Experiment(_round_cfg(type="loan", batch_size=8, no_models=3,
                              number_of_total_participants=4, lr=0.01,
                              data_dir=str(tmp_path)), save_results=False)
    assert not e.loan_data.synthetic
    r = e.run_round(1)
    assert np.isfinite(r["global_acc"])
