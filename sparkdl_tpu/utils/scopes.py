"""The program's scope names, registered where they are opened.

A layer's region of the step is named by ``with scopes.layer("mamba_conv"):``
where the model, the kernel or the step opens it. The name reaches the
compiled program as a path component of every operation traced inside
(``jit(step)/jvp(Model)/layers_3/mamba_conv/mul``) and from there the device
trace; :func:`names` is every name opened so far in this process, which is
what ``runner.analysis`` sorts device time by. No list of names is kept
anywhere else: a model that opens a new scope has registered it.
"""

from __future__ import annotations

import jax

_NAMES: set = set()


def layer(name: str):
    """``jax.named_scope(name)``; ``name`` joins :func:`names`. It runs when
    the enclosing function is traced, never when the compiled step runs."""
    _NAMES.add(name)
    return jax.named_scope(name)


def names() -> frozenset:
    """Every name :func:`layer` was given so far, in this process."""
    return frozenset(_NAMES)
