"""What a compiled program does to a buffer that outlives it.

A KV-cache pool is handed, donated, from program to program for the
whole life of a ``serving.DecodeEngine``, so each of its programs
should work on it in place.  A program re-lays a leaf out instead, at
two passes over the leaf a call, when the order its own work prefers is
not the order the leaf arrives in: until PR 27 the decode step of every
pool did (PERF.md, section 6).  The cache is now declared in the order
the step works in (``models.transformer.SelfAttention``); this module
holds the check that it stays so: the count of whole-leaf copies in a
compiled program's HLO text, and a one-line description of the layout a
pytree of arrays lives in.

Why the order is declared and not read from the compiler
(``jax.experimental.layout``, ``Layout.AUTO`` on the step's cache
argument, the pool allocated in what comes back, every other program
pinned to it): that was built first and ran on the chip at the same
speed, but an executable read back from JAX's persistent compilation
cache does not keep a result layout other than the default one
(jax 0.9.0, on the CPU and on the v5e: a pool allocated by a cached
program arrived row-major and the step refused it), and the cache is
what makes a serving process start in one minute instead of four.
"""

import re

import jax
import jax.numpy as jnp

# "%copy.3 = bf16[32,16,512,128]{3,1,2,0:T(8,128)(2,1)} copy(%p)", or
# "%copy-start.4 = (<to>, <from>, u32[]{:S(2)}) copy-start(%fusion.2)"
_COPY = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(?)(\w+\[([\d,]*)\]\S*)"
    r"(?: (\w+\[[\d,]*\]\S*))?.*? (?:copy|copy-start)\(")
# what two sides of a copy-start may differ in and still be one layout:
# the memory space, and the comma that closes the first tuple element
_MEMORY_SPACE = re.compile(r"S\(\d+\)|,$")


LANES = 128


def lane_padded(width: int) -> int:
    """The width to declare a cache leaf's last axis at, so that
    row-major is the TPU's default layout of the leaf.

    A TPU gives an array whose last axis is wider than its 128 lanes
    and no multiple of them another default layout than row-major (a
    ``bf16[64, 6144, 576]`` latent leaf: positions last), and since the
    order is declared and not read from the compiler (above), every
    program handed the pool then copies each leaf whole on the way in
    and on the way out: 12 whole-leaf copies in a six-layer step at 576
    wide, none at 640 (compiled for a described v5e;
    ``tests/test_layouts.py`` holds both).  The owner of the leaf fills
    the extra columns with zeros."""
    return width if width <= LANES else -(-width // LANES) * LANES


def describe(tree) -> str:
    """One line for a log or a trace: each distinct (dtype, shape,
    layout) among the non-scalar arrays of ``tree``."""
    seen = []
    for a in jax.tree_util.tree_leaves(tree):
        if not a.shape:
            continue
        lay = a.format.layout
        text = (f"{jnp.dtype(a.dtype).name}{list(a.shape)} "
                f"major_to_minor={tuple(lay.major_to_minor)} "
                f"tiling={tuple(lay.tiling or ())}")
        if text not in seen:
            seen.append(text)
    return "; ".join(seen)


def whole_leaf_copies(hlo_text: str, shapes, *, moves: bool = False
                      ) -> int:
    """How many ``copy`` (or asynchronous ``copy-start``) operations of
    a compiled program's HLO text produce a whole leaf of ``shapes``: a
    re-layout, or a second instance, of a buffer the program was meant
    to work on in place.  Fused computations are text too, so a copy
    inside a fusion counts.  Scalars are no leaves here.

    A ``copy-start`` whose two sides differ in their memory space alone
    (``S(1)``) is the compiler moving a leaf into faster memory and
    back, not a re-layout; ``moves=True`` counts those instead."""
    whole = {tuple(s.shape) for s in jax.tree_util.tree_leaves(shapes)
             if s.shape}
    n = 0
    for line in hlo_text.splitlines():
        m = _COPY.match(line)
        if not m or tuple(int(d) for d in m.group(3).split(",")
                          if d) not in whole:
            continue
        to, frm = (_MEMORY_SPACE.sub("", t or "")
                   for t in (m.group(2), m.group(4)))
        n += (bool(m.group(1)) and to == frm) == moves
    return n
