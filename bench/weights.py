"""Random weights from a seed, made on the device in one jitted call, in
the type they are served in.

The tree's structure and shapes are the program's (its abstract
parameters); the values are the benchmark's own, so the reference can
use them without taking anything the program made.  Laws by leaf name:
norm ``scale`` ones, ``b`` zeros, embedding ``table`` N(0, 1/d), dense
``w [..., in, out]`` and an MoE ``router [..., in, experts]`` N(0, 1/in),
and TT cores ``c0..c{d-1}`` of one bundle N(0, s²) with s chosen so the
implied dense matrix has the Glorot variance 2 / (M + N).  A reference
module's ``LAWS`` gives the law of a leaf name none of these covers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_data(seed: int) -> np.ndarray:
    """Threefry key words for a seed of up to 64 bits."""
    seed = int(seed)
    return np.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                      np.uint32)


def _name(entry) -> str:
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def leaf_laws(abstract, extra=None) -> list[tuple[str, float]]:
    """(law, std) of each leaf in flatten order; ``extra`` maps further
    leaf names to fn(shape) -> (law, std)."""
    extra = extra or {}
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    shapes = {tuple(_name(e) for e in path): leaf.shape
              for path, leaf in flat}
    laws = []
    for path, leaf in flat:
        names = tuple(_name(e) for e in path)
        last = names[-1]
        if last == "scale":
            laws.append(("ones", 0.0))
        elif last == "b":
            laws.append(("zeros", 0.0))
        elif last == "table":
            laws.append(("normal", 1.0 / math.sqrt(leaf.shape[-1])))
        elif last in ("w", "router"):
            laws.append(("normal", 1.0 / math.sqrt(leaf.shape[-2])))
        elif len(names) >= 2 and names[-2] == "tt" and last.startswith("c"):
            bundle = [shapes[names[:-1] + (f"c{t}",)]
                      for t in range(sum(1 for k in shapes
                                         if k[:-1] == names[:-1]))]
            d = len(bundle)
            n = math.prod(s[-3] for s in bundle)
            m = math.prod(s[-2] for s in bundle)
            rprod = math.prod(s[-1] for s in bundle[:-1])
            var = 2.0 / (m + n)
            laws.append(("normal", (var / rprod) ** (1.0 / (2 * d))))
        elif last in extra:
            laws.append(extra[last](leaf.shape))
        else:
            raise ValueError(f"no law for parameter {'/'.join(names)}")
    return laws


def make_params(abstract, seed: int, extra_laws=None):
    """Parameters of the tree ``abstract`` (ShapeDtypeStructs) drawn from
    ``seed``: one compiled program, the seed a runtime argument."""
    leaves, treedef = jax.tree.flatten(abstract)
    laws = leaf_laws(abstract, extra_laws)

    def draw(kd):
        key = jax.random.wrap_key_data(kd)
        out = []
        for i, (leaf, (law, std)) in enumerate(zip(leaves, laws)):
            if law == "ones":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif law == "zeros":
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, leaf.shape, leaf.dtype)
                            * jnp.asarray(std, leaf.dtype)))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(draw)(jnp.asarray(key_data(seed)))
