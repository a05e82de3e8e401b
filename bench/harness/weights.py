"""Weights from the seed, made on the device in one jitted call.

The tree's layout belongs to the architecture: the configuration's
plain reference (``bench/reference/<reference>.py``) gives it as
``layout(model)``, leaf path -> (shape, init kind), with the kinds
``gain``, ``bias`` and ``<variance>/<fan_in>``.  The sizes come from the
configuration file, never from the program.

The scales make every part of the forward pass matter to the output,
so that the comparison with the reference sees a fault anywhere on the
served path.  Matrices are normal with std ``1 / sqrt(fan_in)``, so each
projection keeps activations at unit scale, attention scores spread
with std about 1 (the output depends on the context and on the rotary
positions) and the logits have std about 1.  The embedding has std
``1 / sqrt(d_model)``, so the residual stream is the layers' work and
not a copy of the input token (with std-0.02 weights greedy decoding
repeats the input token whatever the context, and a broken cache goes
unseen).  Norm gains are 1 + 0.1 normal and biases 0.1 normal.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import spec as S


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A 32-bit PRNG key derived from the whole of a (possibly large)
    seed."""
    word = np.random.SeedSequence([int(seed) % (1 << 64), stream]
                                  ).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, val in flat.items():
        node = tree
        *heads, leaf = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[leaf] = val
    return tree


def _make(key: jax.Array, model_items: Tuple, ref_file: str
          ) -> Dict[str, Any]:
    model = dict(model_items)
    dtype = jnp.dtype(model["param_dtype"])
    flat = {}
    layout = S.reference_at(ref_file).layout(model)
    for i, (path, (shape, kind)) in enumerate(sorted(layout.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        if kind == "gain":
            flat[path] = 1.0 + 0.1 * z
        elif kind == "bias":
            flat[path] = 0.1 * z
        else:
            var, fan_in = kind.split("/")
            flat[path] = (float(var) / float(fan_in)) ** 0.5 * z
    return _nest(flat)


def model_items(model: Dict[str, Any]) -> Tuple:
    """The configuration's scalar sizes, hashable (a static jit key)."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if not isinstance(v, (dict, list))))


@functools.lru_cache(maxsize=None)
def _jitted(model_items: Tuple, ref_file: str):
    return jax.jit(functools.partial(_make, model_items=model_items,
                                     ref_file=ref_file))


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The parameter tree of configuration ``config`` for ``seed``, made
    on the default device."""
    return _jitted(model_items(config["model"]),
                   S.reference_file(config))(key_from_seed(seed))


def check_layout(params_shapes: Any, config: Dict[str, Any]) -> None:
    """Raise unless the program's parameter tree (``jax.eval_shape`` of
    its init) has exactly the reference's layout."""
    layout = S.reference(config).layout(config["model"])
    want = {p: s for p, (s, _) in layout.items()}
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shapes)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        got[name] = tuple(leaf.shape)
    if got != want:
        raise ValueError(f"parameter layout differs from the program's: "
                         f"bench {sorted(want.items())} vs program "
                         f"{sorted(got.items())}")
