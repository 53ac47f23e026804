"""Named-model registry — per-model metadata for the image transformers.

Re-creates the reference's ``keras_applications.py`` registry (SURVEY.md §2.1):
for each supported named model — InceptionV3, Xception, ResNet50, VGG16, VGG19
(+ extra ResNet depths) — the constructor, expected input size, preprocessing
function, and bottleneck feature dimension. The preprocess fns are jnp-pure so
they fuse into the same XLA program as the model forward pass (the reference
ran preprocessing as a separate TF graph piece stitched in front — SURVEY.md
§3.1; here XLA fusion makes the stitch free).

Weights: zero-egress environment → models initialize randomly
(``init_params``); ``save_weights``/``load_weights`` use flax msgpack
serialization, and ``load_safetensors`` imports locally-provided safetensors
files by flattened param path.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import inception, resnet, vgg, xception

IMAGENET_CLASSES = 1000

_CAFFE_MEAN = (103.939, 116.779, 123.68)  # BGR order
_TORCH_MEAN = (0.485, 0.456, 0.406)
_TORCH_STD = (0.229, 0.224, 0.225)


def _as_float(x):
    """Integer image batches (the uint8 wire format — 4x fewer
    host→HBM bytes than f32) upcast IN-GRAPH before the arithmetic:
    without this, caffe's mean subtraction would run in uint8 and WRAP
    (103.94 → 103, 90-103 → 243+), and tf's ``x/127.5`` would rely on
    dtype promotion. XLA fuses the cast into the first op for free."""
    return x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.integer) \
        else x


def preprocess_tf(x):
    """Scale [0,255] → [-1,1] (InceptionV3 / Xception convention)."""
    return _as_float(x) / 127.5 - 1.0


def preprocess_caffe(x):
    """RGB→BGR + ImageNet mean subtraction (ResNet50/VGG convention)."""
    x = _as_float(x)[..., ::-1]
    return x - jnp.asarray(_CAFFE_MEAN, dtype=x.dtype)


def preprocess_torch(x):
    x = _as_float(x) / 255.0
    return (x - jnp.asarray(_TORCH_MEAN, dtype=x.dtype)) / jnp.asarray(
        _TORCH_STD, dtype=x.dtype)


@dataclass(frozen=True)
class NamedImageModel:
    """Metadata + builders for one named model."""
    name: str
    factory: Callable[..., Any]  # (num_classes, dtype) → flax Module
    input_size: tuple[int, int]  # (H, W)
    preprocess: Callable  # jnp [0,255] NHWC float → model input
    feature_dim: int
    num_classes: int = IMAGENET_CLASSES

    def build(self, dtype=jnp.float32, num_classes: int | None = None,
              **build_kwargs):
        """``build_kwargs`` pass through to the flax factory (e.g.
        ``stride_on_3x3=False`` for keras-v1 ResNet semantics when running
        keras-applications weights — models/pretrained.py)."""
        return self.factory(num_classes=num_classes or self.num_classes,
                            dtype=dtype, **build_kwargs)

    def init_params(self, seed: int = 0, dtype=jnp.float32,
                    num_classes: int | None = None, **build_kwargs):
        model = self.build(dtype, num_classes, **build_kwargs)
        h, w = self.input_size

        # jit the init: un-jitted flax init executes (and compiles) op by
        # op; as one compiled program it is a single compile.
        @jax.jit
        def init(key):
            return model.init(key, jnp.zeros((1, h, w, 3), jnp.float32),
                              train=False)

        return init(jax.random.PRNGKey(seed))

    def apply_fn(self, dtype=jnp.float32, features_only: bool = False,
                 with_preprocess: bool = True,
                 num_classes: int | None = None, **build_kwargs) -> Callable:
        """Returns jittable ``fn(variables, batch)``; batch is NHWC float32
        in [0,255] when ``with_preprocess`` (the image-struct convention)."""
        model = self.build(dtype, num_classes, **build_kwargs)

        def fn(variables, batch):
            x = self.preprocess(batch) if with_preprocess else batch
            return model.apply(variables, x, train=False,
                               features_only=features_only)

        return fn


SUPPORTED_MODELS: dict[str, NamedImageModel] = {}


def _register(m: NamedImageModel):
    SUPPORTED_MODELS[m.name] = m
    return m


_register(NamedImageModel("InceptionV3", inception.InceptionV3, (299, 299),
                          preprocess_tf, 2048))
_register(NamedImageModel("Xception", xception.Xception, (299, 299),
                          preprocess_tf, 2048))
_register(NamedImageModel("ResNet50", resnet.ResNet50, (224, 224),
                          preprocess_caffe, 2048))
_register(NamedImageModel("ResNet18", resnet.ResNet18, (224, 224),
                          preprocess_caffe, 512))
_register(NamedImageModel("ResNet34", resnet.ResNet34, (224, 224),
                          preprocess_caffe, 512))
_register(NamedImageModel("ResNet101", resnet.ResNet101, (224, 224),
                          preprocess_caffe, 2048))
_register(NamedImageModel("ResNet152", resnet.ResNet152, (224, 224),
                          preprocess_caffe, 2048))
_register(NamedImageModel("VGG16", vgg.VGG16, (224, 224),
                          preprocess_caffe, 4096))
_register(NamedImageModel("VGG19", vgg.VGG19, (224, 224),
                          preprocess_caffe, 4096))


def get_model(name: str) -> NamedImageModel:
    try:
        return SUPPORTED_MODELS[name]
    except KeyError:
        raise ValueError(
            f"Unknown model {name!r}; supported: {sorted(SUPPORTED_MODELS)}"
        ) from None


def decodePredictions(logits: np.ndarray, top: int = 5) -> list[list[dict]]:
    """Top-k decode of classifier logits (DeepImagePredictor's
    ``decodePredictions``). Offline environment → numeric class ids, not the
    ImageNet label text the reference downloaded."""
    logits = np.asarray(logits)
    probs = np.exp(logits - logits.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    out = []
    for row in probs:
        idx = np.argsort(row)[::-1][:top]
        out.append([{"class": int(i), "label": f"class_{int(i)}",
                     "score": float(row[i])} for i in idx])
    return out


# ---------------------------------------------------------------------------
# LLM family metadata — draft/target pairing for speculative serving
# ---------------------------------------------------------------------------
# The image registry above names vision models; the generation stack's
# families live in ``models.llama`` as config constructors. Speculative
# decoding (serving.draft.DraftModelProvider) needs a DRAFT model per
# target family — registry-driven so deployments swap pairings without
# touching engine code. Names: ``llama3_8b`` / ``llama_small``
# (TinyLlama-shaped ~1B) / ``llama_tiny`` (test scale).

# target family -> draft family (each one tier down: the draft must be
# cheap relative to its target or speculation cannot pay)
DRAFT_PAIRS: dict[str, str] = {
    "llama3_8b": "llama_small",
    "llama_small": "llama_tiny",
}


def register_draft_pair(target: str, draft: str) -> None:
    """Name ``draft`` as the speculative draft family for ``target``
    (overwrites an existing pairing — deployments tune this)."""
    if target == draft:
        raise ValueError(f"{target!r} cannot draft for itself — a draft "
                         f"model the size of its target saves nothing")
    DRAFT_PAIRS[str(target)] = str(draft)


def draft_for(model_name: str) -> str | None:
    """The registered draft family for ``model_name`` (None when the
    family has no pairing — the engine then uses n-gram
    self-drafting)."""
    return DRAFT_PAIRS.get(model_name)


def llm_config(name: str):
    """Named LLM config constructor (``models.llama.LlamaConfig``
    classmethods). Lazy import: the image-model paths never pay it."""
    from .llama import LlamaConfig
    factories = {"llama3_8b": LlamaConfig.llama3_8b,
                 "llama_small": LlamaConfig.small,
                 "llama_tiny": LlamaConfig.tiny}
    try:
        factory = factories[name]
    except KeyError:
        raise ValueError(f"Unknown LLM config {name!r}; supported: "
                         f"{sorted(factories)}") from None
    return factory()


# ---------------------------------------------------------------------------
# Weight persistence (flax msgpack + safetensors import)
# ---------------------------------------------------------------------------

def save_weights(variables, path: str):
    from flax import serialization
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "wb") as f:
        f.write(serialization.to_bytes(variables))


def load_weights(variables_template, path: str):
    from flax import serialization
    with open(path, "rb") as f:
        return serialization.from_bytes(variables_template, f.read())


def load_safetensors(variables_template, path: str):
    """Import a safetensors file whose keys are '/'-joined flax param paths."""
    from flax.traverse_util import flatten_dict, unflatten_dict
    from safetensors.numpy import load_file
    loaded = load_file(path)
    flat = flatten_dict(variables_template, sep="/")
    missing = [k for k in flat if k not in loaded]
    if missing:
        raise ValueError(f"safetensors file missing {len(missing)} keys, "
                         f"e.g. {missing[:3]}")
    out = {}
    for k, tmpl in flat.items():
        arr = jnp.asarray(loaded[k])
        if arr.shape != tmpl.shape:
            # No silent reshape: a same-size transposed tensor (e.g. a torch
            # OI export vs flax IO) would load as garbage.
            raise ValueError(f"Shape mismatch for {k}: file has {arr.shape}, "
                             f"model expects {tmpl.shape}")
        out[k] = arr
    return unflatten_dict({tuple(k.split("/")): v for k, v in out.items()})


def save_safetensors(variables, path: str):
    from flax.traverse_util import flatten_dict
    from safetensors.numpy import save_file
    flat = flatten_dict(variables, sep="/")
    save_file({k: np.asarray(v) for k, v in flat.items()}, path)
