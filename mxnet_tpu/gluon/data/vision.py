"""Vision datasets + transforms (reference: mxnet/gluon/data/vision/*).

Datasets read the standard on-disk formats when present (MNIST idx files,
CIFAR binary batches); with no files and no network egress they fall back to
a deterministic synthetic set with the right shapes/cardinality so training
scripts and tests run unchanged.
"""
from __future__ import annotations

import gzip
import os
import struct

import numpy as _np

from ...ndarray import NDArray, array
from .dataset import Dataset, ArrayDataset

__all__ = ["MNIST", "FashionMNIST", "CIFAR10", "CIFAR100",
           "ImageRecordDataset", "ImageFolderDataset", "transforms"]


class _DownloadedDataset(Dataset):
    def __init__(self, root, train, transform=None):
        self._root = os.path.expanduser(root)
        self._train = train
        self._transform = transform
        self._data = None
        self._label = None
        self._get_data()

    def __len__(self):
        return len(self._label)

    def __getitem__(self, idx):
        # samples are host numpy: the transform chain mirrors the
        # type, so the whole pipeline stays on the host and the
        # DataLoader device-puts once per BATCH (one transfer a batch
        # where per-sample NDArrays cost one a sample). The .copy()
        # isolates the shared dataset buffer from in-place transforms
        # (a mutating transform must not corrupt later epochs).
        d = self._data[idx].copy()
        l = self._label[idx]
        if self._transform is not None:
            return self._transform(d, l)
        return d, l


def _synthetic(n, shape, num_classes, seed):
    """Separable synthetic fallback: class id is bit-stamped into corner
    blocks, so LeNet-class models reach >95% — keeps integration tests
    meaningful without the real files."""
    rng = _np.random.RandomState(seed)
    data = (rng.rand(n, *shape) * 64).astype(_np.uint8)  # dim noise
    label = rng.randint(0, num_classes, n).astype(_np.int32)
    nbits = max(int(_np.ceil(_np.log2(max(num_classes, 2)))), 1)
    bs = max(min(shape[0], shape[1]) // (nbits + 1), 2)  # block size
    for c in range(num_classes):
        sel = label == c
        for b in range(nbits):
            if (c >> b) & 1:
                data[sel, b * bs:(b + 1) * bs, :bs] = 255
    return data, label


class MNIST(_DownloadedDataset):
    """reference: gluon/data/vision/datasets.py::MNIST (idx-ubyte files)."""

    _num_classes = 10
    _shape = (28, 28, 1)
    _n_train, _n_test = 60000, 10000

    def __init__(self, root="~/.mxnet/datasets/mnist", train=True,
                 transform=None):
        super().__init__(root, train, transform)

    def _files(self):
        if self._train:
            return ("train-images-idx3-ubyte", "train-labels-idx1-ubyte")
        return ("t10k-images-idx3-ubyte", "t10k-labels-idx1-ubyte")

    def _get_data(self):
        imgf, labf = self._files()

        def find(name):
            for cand in (os.path.join(self._root, name),
                         os.path.join(self._root, name + ".gz")):
                if os.path.exists(cand):
                    return cand
            return None

        fi, fl = find(imgf), find(labf)
        if fi and fl:
            self._data = self._read_images(fi)
            self._label = self._read_labels(fl)
            return
        n = 6000 if self._train else 1000  # synthetic fallback (scaled)
        self._data, self._label = _synthetic(n, self._shape,
                                             self._num_classes,
                                             42 if self._train else 43)

    @staticmethod
    def _open(path):
        return gzip.open(path, "rb") if path.endswith(".gz") \
            else open(path, "rb")

    def _read_images(self, path):
        with self._open(path) as f:
            _, n, r, c = struct.unpack(">IIII", f.read(16))
            d = _np.frombuffer(f.read(), dtype=_np.uint8)
        return d.reshape(n, r, c, 1)

    def _read_labels(self, path):
        with self._open(path) as f:
            struct.unpack(">II", f.read(8))
            return _np.frombuffer(f.read(), dtype=_np.uint8).astype(
                _np.int32)


class FashionMNIST(MNIST):
    def __init__(self, root="~/.mxnet/datasets/fashion-mnist", train=True,
                 transform=None):
        super().__init__(root, train, transform)


class CIFAR10(_DownloadedDataset):
    """reference: CIFAR10 binary batches."""

    _num_classes = 10
    _shape = (32, 32, 3)

    def __init__(self, root="~/.mxnet/datasets/cifar10", train=True,
                 transform=None):
        super().__init__(root, train, transform)

    def _get_data(self):
        names = [f"data_batch_{i}.bin" for i in range(1, 6)] \
            if self._train else ["test_batch.bin"]
        paths = [os.path.join(self._root, "cifar-10-batches-bin", n)
                 for n in names]
        if all(os.path.exists(p) for p in paths):
            datas, labels = [], []
            for p in paths:
                raw = _np.fromfile(p, dtype=_np.uint8).reshape(-1, 3073)
                labels.append(raw[:, 0].astype(_np.int32))
                datas.append(raw[:, 1:].reshape(-1, 3, 32, 32)
                             .transpose(0, 2, 3, 1))
            self._data = _np.concatenate(datas)
            self._label = _np.concatenate(labels)
            return
        n = 5000 if self._train else 1000
        self._data, self._label = _synthetic(n, self._shape,
                                             self._num_classes,
                                             44 if self._train else 45)


class CIFAR100(CIFAR10):
    _num_classes = 100

    def __init__(self, root="~/.mxnet/datasets/cifar100", train=True,
                 transform=None, fine_label=True):
        super().__init__(root, train, transform)

    def _get_data(self):
        n = 5000 if self._train else 1000
        self._data, self._label = _synthetic(n, self._shape,
                                             self._num_classes,
                                             46 if self._train else 47)


class ImageRecordDataset(Dataset):
    """RecordIO-backed image dataset (reference: ImageRecordDataset).
    Records are (header, payload) packed by runtime/recordio.pack_img —
    payload is raw HWC uint8 (no JPEG dependency in this image)."""

    def __init__(self, filename, flag=1, transform=None):
        from ...runtime import recordio
        self._rec = recordio.IndexedRecordIO(filename + ".idx", filename,
                                             "r")
        self._transform = transform

    def __len__(self):
        return len(self._rec.keys)

    def __getitem__(self, idx):
        from ...runtime import recordio
        item = self._rec.read_idx(self._rec.keys[idx])
        header, img = recordio.unpack_img(item)
        l = _np.float32(header.label) if _np.isscalar(header.label) \
            else header.label
        if self._transform:
            return self._transform(img, l)
        return img, l


class ImageFolderDataset(Dataset):
    """reference: ImageFolderDataset (folder-per-class, via PIL)."""

    def __init__(self, root, flag=1, transform=None):
        self._root = os.path.expanduser(root)
        self._transform = transform
        self.synsets = []
        self.items = []
        for folder in sorted(os.listdir(self._root)):
            path = os.path.join(self._root, folder)
            if not os.path.isdir(path):
                continue
            label = len(self.synsets)
            self.synsets.append(folder)
            for fn in sorted(os.listdir(path)):
                self.items.append((os.path.join(path, fn), label))

    def __len__(self):
        return len(self.items)

    def __getitem__(self, idx):
        from PIL import Image
        path, label = self.items[idx]
        img = _np.asarray(Image.open(path).convert("RGB"))
        if self._transform:
            return self._transform(img, label)
        return img, label


def _as_np(x):
    return x.asnumpy() if isinstance(x, NDArray) else _np.asarray(x)


def _like(out, ref):
    """Mirror the input container type: NDArray in -> NDArray out
    (upstream-compatible for direct callers); numpy in -> numpy out,
    which keeps the DataLoader pipeline on the host — samples stay
    there through the whole transform chain and the batchify does ONE
    device put per batch instead of two transfers per sample."""
    return array(out) if isinstance(ref, NDArray) else out


#: single source for the numerically load-bearing constants: the
#: mx.image module owns them (plain host numpy — importing costs no
#: JAX backend init), which keeps the seed-parity guarantee between
#: the two augmenter implementations drift-free
from ...image import (_GRAY_COEF as _LUMA, _TYIQ, _ITYIQ,  # noqa: E402
                      _IMAGENET_EIGVAL, _IMAGENET_EIGVEC)


class transforms:
    """reference: gluon/data/vision/transforms.py. All host-side numpy
    — the preferred input-pipeline path (mx.image keeps the legacy
    NDArray/jnp augmenters). Output type mirrors input type; the same
    np.random draw sequence as the mx.image augmenters keeps the two
    implementations numerically interchangeable under one seed."""

    class Compose:
        def __init__(self, transforms_list):
            self._ts = transforms_list

        def __call__(self, x):
            for t in self._ts:
                x = t(x)
            return x

    class ToTensor:
        """HWC uint8 [0,255] -> float32 [0,1]. Default layout "CHW"
        matches the reference; pass layout="NHWC" (or "HWC") to keep
        channels-last — the natural layout for TPU convolutions."""

        def __init__(self, layout="CHW"):
            self._chw = layout.upper().lstrip("N") == "CHW"

        def __call__(self, x):
            a = _as_np(x).astype(_np.float32) / 255.0
            return _like(_np.moveaxis(a, -1, 0) if self._chw else a, x)

    class Normalize:
        """Per-channel normalization. layout="CHW" (the reference's
        default, matching CHW ToTensor output) reshapes vector
        mean/std to (C, 1, 1); layout="NHWC"/"HWC" broadcasts them
        over the trailing channel axis — explicit, not guessed, so a
        (3, H, 3) image can never be normalized along the wrong
        axis."""

        def __init__(self, mean=0.0, std=1.0, layout="CHW"):
            self._mean = _np.asarray(mean, _np.float32)
            self._std = _np.asarray(std, _np.float32)
            self._chw = layout.upper().lstrip("N") == "CHW"

        def __call__(self, x):
            a = _as_np(x)
            m, s = self._mean, self._std
            if self._chw:
                m = m.reshape(-1, 1, 1) if m.ndim else m
                s = s.reshape(-1, 1, 1) if s.ndim else s
            return _like((a - m) / s, x)

    class Cast:
        def __init__(self, dtype="float32"):
            self._dtype = dtype

        def __call__(self, x):
            if isinstance(x, NDArray):
                return x.astype(self._dtype)
            return _np.asarray(x).astype(self._dtype)

    class Resize:
        def __init__(self, size, keep_ratio=False, interpolation=1):
            self._size = (size, size) if isinstance(size, int) else size

        def __call__(self, x):
            a = _as_np(x)
            h, w = self._size[1], self._size[0]
            ys = (_np.linspace(0, a.shape[0] - 1, h)).astype(_np.int64)
            xs = (_np.linspace(0, a.shape[1] - 1, w)).astype(_np.int64)
            return _like(a[ys][:, xs], x)

    class CenterCrop:
        def __init__(self, size):
            self._size = (size, size) if isinstance(size, int) else size

        def __call__(self, x):
            a = _as_np(x)
            w, h = self._size
            y0 = max((a.shape[0] - h) // 2, 0)
            x0 = max((a.shape[1] - w) // 2, 0)
            return _like(a[y0:y0 + h, x0:x0 + w], x)

    class RandomResizedCrop:
        def __init__(self, size, scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3),
                     interpolation=1):
            self._size = (size, size) if isinstance(size, int) else size
            self._scale = scale
            self._ratio = ratio

        def __call__(self, x):
            a = _as_np(x)
            H, W = a.shape[:2]
            area = H * W
            for _ in range(10):
                target = area * _np.random.uniform(*self._scale)
                ar = _np.random.uniform(*self._ratio)
                w = int(round(_np.sqrt(target * ar)))
                h = int(round(_np.sqrt(target / ar)))
                if w <= W and h <= H:
                    x0 = _np.random.randint(0, W - w + 1)
                    y0 = _np.random.randint(0, H - h + 1)
                    crop = a[y0:y0 + h, x0:x0 + w]
                    break
            else:
                crop = a
            ys = _np.linspace(0, crop.shape[0] - 1,
                              self._size[1]).astype(_np.int64)
            xs = _np.linspace(0, crop.shape[1] - 1,
                              self._size[0]).astype(_np.int64)
            return _like(crop[ys][:, xs], x)

    class RandomFlipLeftRight:
        def __call__(self, x):
            a = _as_np(x)
            if _np.random.rand() < 0.5:
                a = a[:, ::-1].copy()
            return _like(a, x)

    class RandomFlipTopBottom:
        def __call__(self, x):
            a = _as_np(x)
            if _np.random.rand() < 0.5:
                a = a[::-1].copy()
            return _like(a, x)

    # color-space transforms (reference: gluon/data/vision/transforms
    # RandomBrightness/.../RandomLighting). Same math and the same
    # np.random draw ORDER as the mx.image augmenters (parity-tested),
    # but in host numpy: per-sample jnp dispatch is what made the
    # legacy path slow.
    class RandomBrightness:
        def __init__(self, brightness):
            self._b = brightness

        def __call__(self, x):
            alpha = 1.0 + _np.random.uniform(-self._b, self._b)
            return _like(_as_np(x).astype(_np.float32) * alpha, x)

    class RandomContrast:
        def __init__(self, contrast):
            self._c = contrast

        def __call__(self, x):
            alpha = 1.0 + _np.random.uniform(-self._c, self._c)
            a = _as_np(x).astype(_np.float32)
            gray = float(_np.sum(a * _LUMA)) * \
                (3.0 * (1.0 - alpha) / a.size)
            return _like(a * alpha + _np.float32(gray), x)

    class RandomSaturation:
        def __init__(self, saturation):
            self._s = saturation

        def __call__(self, x):
            alpha = 1.0 + _np.random.uniform(-self._s, self._s)
            a = _as_np(x).astype(_np.float32)
            gray = _np.sum(a * _LUMA, axis=2, keepdims=True) * \
                _np.float32(1.0 - alpha)
            return _like(a * alpha + gray, x)

    class RandomHue:
        def __init__(self, hue):
            self._h = hue

        def __call__(self, x):
            alpha = _np.random.uniform(-self._h, self._h)
            u = _np.cos(alpha * _np.pi)
            w = _np.sin(alpha * _np.pi)
            bt = _np.array([[1.0, 0.0, 0.0],
                            [0.0, u, -w],
                            [0.0, w, u]], _np.float32)
            t = (_ITYIQ @ bt @ _TYIQ).T
            return _like(_as_np(x).astype(_np.float32) @ t, x)

    class RandomColorJitter:
        def __init__(self, brightness=0, contrast=0, saturation=0,
                     hue=0):
            ts = []
            if brightness > 0:
                ts.append(transforms.RandomBrightness(brightness))
            if contrast > 0:
                ts.append(transforms.RandomContrast(contrast))
            if saturation > 0:
                ts.append(transforms.RandomSaturation(saturation))
            self._ts = ts
            self._hue = transforms.RandomHue(hue) if hue else None

        def __call__(self, x):
            for i in _np.random.permutation(len(self._ts)):
                x = self._ts[int(i)](x)
            return self._hue(x) if self._hue is not None else x

    class RandomLighting:
        def __init__(self, alpha, eigval=None, eigvec=None):
            self._std = alpha
            self._eigval = _np.asarray(
                _IMAGENET_EIGVAL if eigval is None else eigval,
                _np.float32)
            self._eigvec = _np.asarray(
                _IMAGENET_EIGVEC if eigvec is None else eigvec,
                _np.float32)

        def __call__(self, x):
            alpha = _np.random.normal(0.0, self._std, size=(3,)) \
                .astype(_np.float32)
            rgb = self._eigvec @ (alpha * self._eigval)
            return _like(_as_np(x).astype(_np.float32) + rgb, x)
