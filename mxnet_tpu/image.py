"""mx.image (reference: mxnet/image/image.py) — decode/resize/crop
utilities and augmenters over NDArray images (HWC uint8/float).

TPU-first notes: `imresize` uses jax.image.resize (runs on device, XLA
fuses with downstream casts); decode rides PIL on the host like the
reference rides OpenCV. The Gluon path (gluon.data.vision.transforms)
is preferred for new code; this module keeps legacy scripts running.
"""
from __future__ import annotations

import io as _io
from typing import Optional, Sequence

import numpy as _np

import jax
import jax.numpy as jnp

from .ndarray import NDArray, array

__all__ = ["imdecode", "imread", "imresize", "resize_short",
           "fixed_crop", "center_crop", "random_crop",
           "color_normalize", "HorizontalFlipAug", "CastAug",
           "ResizeAug", "CenterCropAug", "RandomCropAug",
           "ColorNormalizeAug", "BrightnessJitterAug",
           "ContrastJitterAug", "SaturationJitterAug", "HueJitterAug",
           "ColorJitterAug", "LightingAug", "RandomOrderAug",
           "CreateAugmenter", "ImageIter"]


def imdecode(buf, to_rgb=True, flag=1, **kw) -> NDArray:
    """Decode a compressed image buffer (JPEG/PNG) to HWC uint8."""
    from PIL import Image
    img = Image.open(_io.BytesIO(bytes(buf)))
    img = img.convert("RGB" if flag else "L")
    a = _np.asarray(img)
    if a.ndim == 2:
        a = a[:, :, None]
    if not to_rgb and a.shape[2] == 3:
        a = a[:, :, ::-1]
    return array(a)


def imread(filename, flag=1, to_rgb=True) -> NDArray:
    with open(filename, "rb") as f:
        return imdecode(f.read(), to_rgb=to_rgb, flag=flag)


def _raw(img):
    return img._data if isinstance(img, NDArray) else jnp.asarray(img)


def imresize(src, w, h, interp=1) -> NDArray:
    """Resize HWC to (h, w). interp 0=nearest else bilinear."""
    a = _raw(src)
    method = "nearest" if interp == 0 else "linear"
    out = jax.image.resize(a.astype(jnp.float32),
                           (h, w, a.shape[2]), method=method)
    if jnp.issubdtype(a.dtype, jnp.integer):
        out = jnp.clip(jnp.round(out), 0, 255).astype(a.dtype)
    return NDArray(out)


def resize_short(src, size, interp=1) -> NDArray:
    a = _raw(src)
    H, W = a.shape[:2]
    if H <= W:
        nh, nw = size, int(W * size / H)
    else:
        nh, nw = int(H * size / W), size
    return imresize(src, nw, nh, interp)


def fixed_crop(src, x0, y0, w, h, size=None, interp=1) -> NDArray:
    a = _raw(src)[y0:y0 + h, x0:x0 + w]
    out = NDArray(a)
    if size is not None and (w, h) != size:
        out = imresize(out, size[0], size[1], interp)
    return out


def center_crop(src, size, interp=1):
    a = _raw(src)
    H, W = a.shape[:2]
    w, h = size
    x0 = max((W - w) // 2, 0)
    y0 = max((H - h) // 2, 0)
    return fixed_crop(src, x0, y0, min(w, W), min(h, H), size,
                      interp), (x0, y0, w, h)


def random_crop(src, size, interp=1):
    a = _raw(src)
    H, W = a.shape[:2]
    w, h = size
    x0 = int(_np.random.randint(0, max(W - w, 0) + 1))
    y0 = int(_np.random.randint(0, max(H - h, 0) + 1))
    return fixed_crop(src, x0, y0, min(w, W), min(h, H), size,
                      interp), (x0, y0, w, h)


def color_normalize(src, mean, std=None) -> NDArray:
    a = _raw(src).astype(jnp.float32)
    a = a - jnp.asarray(mean, jnp.float32)
    if std is not None:
        a = a / jnp.asarray(std, jnp.float32)
    return NDArray(a)


# -- augmenter objects (reference: image.py Augmenter classes) -------------
class Augmenter:
    def __call__(self, src):
        raise NotImplementedError


class ResizeAug(Augmenter):
    def __init__(self, size, interp=1):
        self.size, self.interp = size, interp

    def __call__(self, src):
        return resize_short(src, self.size, self.interp)


class CenterCropAug(Augmenter):
    def __init__(self, size, interp=1):
        self.size, self.interp = size, interp

    def __call__(self, src):
        return center_crop(src, self.size, self.interp)[0]


class RandomCropAug(Augmenter):
    def __init__(self, size, interp=1):
        self.size, self.interp = size, interp

    def __call__(self, src):
        return random_crop(src, self.size, self.interp)[0]


class HorizontalFlipAug(Augmenter):
    def __init__(self, p=0.5):
        self.p = p

    def __call__(self, src):
        if _np.random.rand() < self.p:
            return NDArray(jnp.flip(_raw(src), axis=1))
        return src if isinstance(src, NDArray) else NDArray(_raw(src))


class CastAug(Augmenter):
    def __init__(self, typ="float32"):
        self.typ = typ

    def __call__(self, src):
        return NDArray(_raw(src).astype(self.typ))


class ColorNormalizeAug(Augmenter):
    def __init__(self, mean, std):
        self.mean, self.std = mean, std

    def __call__(self, src):
        return color_normalize(src, self.mean, self.std)


# -- color-space augmenters (reference: image.py Brightness/Contrast/
# Saturation/Hue/ColorJitter/Lighting/RandomOrder Aug classes; the
# image-classification examples drive them via aug_level). Randomness
# comes from numpy's global RNG (seed with np.random.seed for
# determinism, same as the crop/flip augmenters above); the pixel math
# runs in fp32 on jnp so XLA can fuse it with downstream casts. -------

#: ITU-R BT.601 luma coefficients, shaped to broadcast over HWC.
#: Kept as numpy: a jnp array here would force JAX backend init (and
#: take the chip) at `import mxnet_tpu` time; jnp ops convert it lazily
#: inside __call__.
_GRAY_COEF = _np.asarray([[[0.299, 0.587, 0.114]]], _np.float32)


class BrightnessJitterAug(Augmenter):
    """Scale pixels by 1 + U(-brightness, brightness)."""

    def __init__(self, brightness):
        self.brightness = brightness

    def __call__(self, src):
        alpha = 1.0 + _np.random.uniform(-self.brightness,
                                         self.brightness)
        return NDArray(_raw(src).astype(jnp.float32) * alpha)


class ContrastJitterAug(Augmenter):
    """Blend with the image's mean luma: alpha*src + (1-alpha)*mean."""

    def __init__(self, contrast):
        self.contrast = contrast

    def __call__(self, src):
        alpha = 1.0 + _np.random.uniform(-self.contrast, self.contrast)
        a = _raw(src).astype(jnp.float32)
        gray = jnp.sum(a * _GRAY_COEF) * (3.0 * (1.0 - alpha) / a.size)
        return NDArray(a * alpha + gray)


class SaturationJitterAug(Augmenter):
    """Blend each pixel with its own luma (gray images are fixed
    points: for equal channels the output equals the input)."""

    def __init__(self, saturation):
        self.saturation = saturation

    def __call__(self, src):
        alpha = 1.0 + _np.random.uniform(-self.saturation,
                                         self.saturation)
        a = _raw(src).astype(jnp.float32)
        gray = jnp.sum(a * _GRAY_COEF, axis=2, keepdims=True) \
            * (1.0 - alpha)
        return NDArray(a * alpha + gray)


#: RGB<->YIQ for the hue rotation (reference: image.py HueJitterAug)
_TYIQ = _np.array([[0.299, 0.587, 0.114],
                   [0.596, -0.274, -0.321],
                   [0.211, -0.523, 0.311]], _np.float32)
_ITYIQ = _np.array([[1.0, 0.956, 0.621],
                    [1.0, -0.272, -0.647],
                    [1.0, -1.107, 1.705]], _np.float32)


class HueJitterAug(Augmenter):
    """Rotate chroma in YIQ by U(-hue, hue)*pi; luma (and therefore
    gray images) are invariant."""

    def __init__(self, hue):
        self.hue = hue

    def __call__(self, src):
        alpha = _np.random.uniform(-self.hue, self.hue)
        u = _np.cos(alpha * _np.pi)
        w = _np.sin(alpha * _np.pi)
        bt = _np.array([[1.0, 0.0, 0.0],
                        [0.0, u, -w],
                        [0.0, w, u]], _np.float32)
        t = (_ITYIQ @ bt @ _TYIQ).T
        a = _raw(src).astype(jnp.float32)
        return NDArray(a @ jnp.asarray(t))


class RandomOrderAug(Augmenter):
    """Apply child augmenters in a random order each call."""

    def __init__(self, ts):
        self.ts = list(ts)

    def __call__(self, src):
        order = _np.random.permutation(len(self.ts))
        for i in order:
            src = self.ts[int(i)](src)
        return src


def ColorJitterAug(brightness, contrast, saturation):
    """Brightness/contrast/saturation jitters in random order."""
    ts = []
    if brightness > 0:
        ts.append(BrightnessJitterAug(brightness))
    if contrast > 0:
        ts.append(ContrastJitterAug(contrast))
    if saturation > 0:
        ts.append(SaturationJitterAug(saturation))
    return RandomOrderAug(ts)


#: ImageNet PCA eigenvalues/vectors (reference defaults)
_IMAGENET_EIGVAL = _np.array([55.46, 4.794, 1.148], _np.float32)
_IMAGENET_EIGVEC = _np.array([[-0.5675, 0.7192, 0.4009],
                              [-0.5808, -0.0045, -0.8140],
                              [-0.5836, -0.6948, 0.4203]], _np.float32)


class LightingAug(Augmenter):
    """AlexNet-style PCA noise: add eigvec @ (N(0, alphastd) * eigval)
    per image (reference: image.py LightingAug)."""

    def __init__(self, alphastd, eigval=None, eigvec=None):
        self.alphastd = alphastd
        self.eigval = _np.asarray(
            _IMAGENET_EIGVAL if eigval is None else eigval, _np.float32)
        self.eigvec = _np.asarray(
            _IMAGENET_EIGVEC if eigvec is None else eigvec, _np.float32)

    def __call__(self, src):
        alpha = _np.random.normal(0.0, self.alphastd, size=(3,)) \
            .astype(_np.float32)
        rgb = self.eigvec @ (alpha * self.eigval)
        return NDArray(_raw(src).astype(jnp.float32)
                       + jnp.asarray(rgb))


def CreateAugmenter(data_shape, resize=0, rand_crop=False,
                    rand_mirror=False, mean=None, std=None,
                    brightness=0, contrast=0, saturation=0, hue=0,
                    pca_noise=0, **kw):
    """Build the standard augmenter list (reference signature subset,
    now incl. the color-space knobs the aug_level presets use)."""
    auglist = []
    if resize > 0:
        auglist.append(ResizeAug(resize))
    crop = (data_shape[2], data_shape[1])
    auglist.append(RandomCropAug(crop) if rand_crop
                   else CenterCropAug(crop))
    if rand_mirror:
        auglist.append(HorizontalFlipAug(0.5))
    auglist.append(CastAug())
    if brightness or contrast or saturation:
        auglist.append(ColorJitterAug(brightness, contrast, saturation))
    if hue:
        auglist.append(HueJitterAug(hue))
    if pca_noise > 0:
        auglist.append(LightingAug(pca_noise))
    if mean is not None:
        auglist.append(ColorNormalizeAug(mean, std))
    return auglist


def ImageIter(*args, **kwargs):
    """reference: image.ImageIter — RecordIO-backed image iterator."""
    from .io import ImageRecordIter
    return ImageRecordIter(*args, **kwargs)
