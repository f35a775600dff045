"""Vision datasets (parity: python/paddle/vision/datasets/ — MNIST, Cifar10,
FashionMNIST, Flowers...). This environment has no network egress, so each
dataset loads from a local file when present and otherwise falls back to a
deterministic synthetic sample generator with the right shapes/classes
(keeps the e2e training paths exercisable anywhere).
"""
from __future__ import annotations

import gzip
import os
import struct

import numpy as np

from ...io import Dataset

__all__ = ["MNIST", "FashionMNIST", "Cifar10", "Cifar100", "FakeImageDataset"]


class FakeImageDataset(Dataset):
    """Deterministic synthetic image classification dataset."""

    def __init__(self, num_samples=1024, image_shape=(1, 28, 28), num_classes=10,
                 transform=None, seed=0):
        self.num_samples = num_samples
        self.image_shape = tuple(image_shape)
        self.num_classes = num_classes
        self.transform = transform
        rng = np.random.RandomState(seed)
        self._labels = rng.randint(0, num_classes, size=num_samples).astype(np.int64)
        self._seeds = rng.randint(0, 2 ** 31 - 1, size=num_samples)

    def __getitem__(self, idx):
        rng = np.random.RandomState(self._seeds[idx])
        label = self._labels[idx]
        # class-dependent mean so the task is learnable
        img = rng.randn(*self.image_shape).astype(np.float32) * 0.5 + \
            (label / self.num_classes - 0.5)
        if self.transform is not None:
            img = self.transform(img)
        return img, np.asarray(label)

    def __len__(self):
        return self.num_samples


class MNIST(Dataset):
    """parity: python/paddle/vision/datasets/mnist.py. Reads the standard IDX
    files from ``image_path``/``label_path`` if given or found under
    ``<cache_dir>/mnist`` (:func:`paddle_tpu.jit.cache.cache_dir`);
    otherwise synthesizes MNIST-shaped data."""

    def __init__(self, image_path=None, label_path=None, mode="train",
                 transform=None, download=True, backend=None):
        self.mode = mode
        self.transform = transform
        from ...jit.cache import cache_dir

        base = os.path.join(cache_dir(), "mnist")
        tag = "train" if mode == "train" else "t10k"
        image_path = image_path or os.path.join(base, f"{tag}-images-idx3-ubyte.gz")
        label_path = label_path or os.path.join(base, f"{tag}-labels-idx1-ubyte.gz")
        if os.path.exists(image_path) and os.path.exists(label_path):
            self.images, self.labels = self._load_idx(image_path, label_path)
            self._fake = None
        else:
            n = 4096 if mode == "train" else 512
            self._fake = FakeImageDataset(n, (1, 28, 28), 10,
                                          seed=0 if mode == "train" else 1)
            self.images = None
            self.labels = None

    @staticmethod
    def _load_idx(image_path, label_path):
        op = gzip.open if image_path.endswith(".gz") else open
        with op(image_path, "rb") as f:
            _, num, rows, cols = struct.unpack(">IIII", f.read(16))
            images = np.frombuffer(f.read(), dtype=np.uint8).reshape(num, rows, cols)
        op = gzip.open if label_path.endswith(".gz") else open
        with op(label_path, "rb") as f:
            _, num = struct.unpack(">II", f.read(8))
            labels = np.frombuffer(f.read(), dtype=np.uint8).astype(np.int64)
        return images, labels

    def __getitem__(self, idx):
        if self._fake is not None:
            return self._fake[idx]
        img = self.images[idx].astype(np.float32)[None] / 255.0
        label = self.labels[idx]
        if self.transform is not None:
            img = self.transform(img)
        return img, np.asarray(label)

    def __len__(self):
        return len(self._fake) if self._fake is not None else len(self.images)


class FashionMNIST(MNIST):
    pass


class _CifarBase(Dataset):
    def __init__(self, data_file=None, mode="train", transform=None,
                 download=True, backend=None, num_classes=10):
        self.transform = transform
        n = 2048 if mode == "train" else 256
        self._fake = FakeImageDataset(n, (3, 32, 32), num_classes,
                                      seed=2 if mode == "train" else 3)

    def __getitem__(self, idx):
        img, label = self._fake[idx]
        if self.transform is not None:
            img = self.transform(img)
        return img, label

    def __len__(self):
        return len(self._fake)


class Cifar10(_CifarBase):
    def __init__(self, data_file=None, mode="train", transform=None,
                 download=True, backend=None):
        super().__init__(data_file, mode, transform, download, backend, 10)


class Cifar100(_CifarBase):
    def __init__(self, data_file=None, mode="train", transform=None,
                 download=True, backend=None):
        super().__init__(data_file, mode, transform, download, backend, 100)


_IMG_EXTENSIONS = (".jpg", ".jpeg", ".png", ".ppm", ".bmp", ".pgm", ".tif",
                   ".tiff", ".webp")


def _scan_files(root, extensions, is_valid_file):
    import os

    exts = tuple(e.lower() for e in (extensions or _IMG_EXTENSIONS))
    out = []
    for dirpath, _, files in sorted(os.walk(root)):
        for f in sorted(files):
            path = os.path.join(dirpath, f)
            ok = (is_valid_file(path) if is_valid_file
                  else f.lower().endswith(exts))
            if ok:
                out.append(path)
    return out


class DatasetFolder(Dataset):
    """parity: vision/datasets/folder.py DatasetFolder — samples arranged in
    class subfolders root/<class>/<file>."""

    def __init__(self, root, loader=None, extensions=None, transform=None,
                 is_valid_file=None):
        import os

        self.root = root
        self.transform = transform
        self.loader = loader or self._default_loader
        classes = sorted(d for d in os.listdir(root)
                         if os.path.isdir(os.path.join(root, d)))
        if not classes:
            raise RuntimeError(f"DatasetFolder: no class folders in {root}")
        self.classes = classes
        self.class_to_idx = {c: i for i, c in enumerate(classes)}
        self.samples = []
        for c in classes:
            for path in _scan_files(os.path.join(root, c), extensions,
                                    is_valid_file):
                self.samples.append((path, self.class_to_idx[c]))
        if not self.samples:
            raise RuntimeError(f"DatasetFolder: no valid files under {root}")

    @staticmethod
    def _default_loader(path):
        from ..__init__ import image_load

        img = image_load(path)
        return np.asarray(img)

    def __getitem__(self, idx):
        path, target = self.samples[idx]
        sample = self.loader(path)
        if self.transform is not None:
            sample = self.transform(sample)
        return sample, target

    def __len__(self):
        return len(self.samples)


class ImageFolder(DatasetFolder):
    """parity: vision/datasets/folder.py ImageFolder — flat folder of
    images, no labels."""

    def __init__(self, root, loader=None, extensions=None, transform=None,
                 is_valid_file=None):
        import os

        self.root = root
        self.transform = transform
        self.loader = loader or DatasetFolder._default_loader
        self.samples = _scan_files(root, extensions, is_valid_file)
        if not self.samples:
            raise RuntimeError(f"ImageFolder: no valid files under {root}")

    def __getitem__(self, idx):
        sample = self.loader(self.samples[idx])
        if self.transform is not None:
            sample = self.transform(sample)
        return [sample]


class Flowers(Dataset):
    """parity: vision/datasets/flowers.py — Oxford-102 over local archives
    (no network egress: pass data_file/label_file/setid_file paths)."""

    def __init__(self, data_file=None, label_file=None, setid_file=None,
                 mode="train", transform=None, download=True, backend=None):
        import os

        self.transform = transform
        for name, f in (("data_file", data_file), ("label_file", label_file),
                        ("setid_file", setid_file)):
            if not (f and os.path.exists(f)):
                raise RuntimeError(
                    "Flowers: no network egress; pass data_file= (102flowers"
                    " tgz), label_file= (imagelabels.mat), setid_file= "
                    f"(setid.mat) — missing {name}")
        from scipy.io import loadmat

        labels = loadmat(label_file)["labels"][0]
        setid = loadmat(setid_file)
        # NB: the reference deliberately swaps trnid/tstid
        # (vision/datasets/flowers.py MODE_FLAG_MAP: train→tstid)
        key = {"train": "tstid", "valid": "valid", "test": "trnid"}[mode]
        self.indexes = setid[key][0]
        self.labels = labels
        self.data_file = data_file
        import tarfile

        self._tf = tarfile.open(data_file)
        self._names = {os.path.basename(n): n
                       for n in self._tf.getnames() if n.endswith(".jpg")}

    def __getitem__(self, idx):
        import io

        from PIL import Image

        img_id = int(self.indexes[idx])
        name = f"image_{img_id:05d}.jpg"
        data = self._tf.extractfile(self._names[name]).read()
        img = np.asarray(Image.open(io.BytesIO(data)))
        label = int(self.labels[img_id - 1])
        if self.transform is not None:
            img = self.transform(img)
        return img, np.asarray([label], np.int64)

    def __len__(self):
        return len(self.indexes)


class VOC2012(Dataset):
    """parity: vision/datasets/voc2012.py — segmentation pairs from the
    VOCtrainval archive (local file; no egress)."""

    def __init__(self, data_file=None, mode="train", transform=None,
                 download=True, backend=None):
        import os
        import tarfile

        self.transform = transform
        if not (data_file and os.path.exists(data_file)):
            raise RuntimeError(
                "VOC2012: no network egress; pass data_file="
                "(VOCtrainval tar)")
        self._tf = tarfile.open(data_file)
        names = self._tf.getnames()
        base = None
        for n in names:
            if n.endswith("ImageSets/Segmentation/train.txt"):
                base = n[:-len("ImageSets/Segmentation/train.txt")]
                break
        if base is None:
            raise RuntimeError("VOC2012: archive missing Segmentation sets")
        part = {"train": "train.txt", "valid": "val.txt",
                "test": "val.txt"}[mode]
        ids = self._tf.extractfile(
            f"{base}ImageSets/Segmentation/{part}").read().decode().split()
        self._base = base
        self.ids = ids

    def __getitem__(self, idx):
        import io

        from PIL import Image

        iid = self.ids[idx]
        img = np.asarray(Image.open(io.BytesIO(self._tf.extractfile(
            f"{self._base}JPEGImages/{iid}.jpg").read())))
        lbl = np.asarray(Image.open(io.BytesIO(self._tf.extractfile(
            f"{self._base}SegmentationClass/{iid}.png").read())))
        if self.transform is not None:
            img = self.transform(img)
        return img, lbl

    def __len__(self):
        return len(self.ids)


__all__ += ["DatasetFolder", "ImageFolder", "Flowers", "VOC2012"]
