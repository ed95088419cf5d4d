"""Seeded weights, named leaf by leaf: the one rule that both the system
under test and the plain references draw from.

Every weight is a function of ``(seed, canonical name)`` only, where the
canonical name is ``<block>/<param>`` (``layer7/wq``, ``res3/c1_w``,
``embed/table``).  The system under test gets all of its weights in one
jitted call on the device, in the type it serves them in
(:func:`program_params`); a reference regenerates any single weight from the
seed alone (:func:`leaf`), so it takes no array that the program made.

The rule is fan-in scaled, so activations keep their scale through depth,
and gives norm scales and batch-norm statistics values away from their
identities, so a program that skipped them would be caught.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def name_hash(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def root_key(seed: int):
    """A PRNG key from any non-negative seed (also past 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _rule(param: str) -> str:
    if param.endswith("_var"):
        return "var"
    if param.endswith("_mean"):
        return "mean"
    if param.endswith("_scale"):
        return "scale"
    if param.endswith("_bias") or param.endswith("_b"):
        return "bias"
    if param == "table":
        return "embed"
    return "fan_in"


def _draw(key, rule: str, shape: Tuple[int, ...]):
    """float32 values of one weight of ``shape`` under ``rule``."""
    if rule == "var":
        return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    if rule == "scale":
        return jax.random.uniform(key, shape, jnp.float32, 0.75, 1.25)
    if rule in ("mean", "bias"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if rule == "embed":
        return jax.random.normal(key, shape, jnp.float32) * shape[-1] ** -0.5
    fan_in = math.prod(shape[:-1]) if len(shape) >= 2 else shape[-1]
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def leaf(seed: int, name: str, shape, dtype=jnp.bfloat16):
    """One weight by canonical name, as served (``dtype``) and then widened
    to float32: the values the system under test holds for that name."""
    param = name.rsplit("/", 1)[1]
    k = jax.random.fold_in(root_key(seed), name_hash(name))
    return _draw(k, _rule(param), tuple(shape)).astype(dtype).astype(
        jnp.float32)


def _layout(cm) -> List[Tuple[str, str, List[str], Tuple[int, ...]]]:
    """(unit key, param key, canonical names, per-layer shape) for every
    leaf of the compiled model's parameter tree; a folded unit stacks one
    name per repetition."""
    from repro.core.lowering import unit_key
    plan = cm.plan
    graph = plan.graph
    out = []
    for unit in plan.units:
        ukey = unit_key(graph, unit)
        if not unit.folded:
            b = graph.blocks[unit.indices[0]]
            for s in b.param_specs():
                out.append((ukey, s.name, [f"{b.name}/{s.name}"],
                            tuple(s.shape)))
            continue
        for j in range(unit.period):
            proto = graph.blocks[unit.indices[j]]
            for s in proto.param_specs():
                names = [f"{graph.blocks[unit.indices[r * unit.period + j]].name}"
                         f"/{s.name}" for r in range(unit.reps)]
                out.append((ukey, f"{j}:{s.name}", names, tuple(s.shape)))
    return out


def program_params(cm, seed: int) -> Dict[str, Any]:
    """The compiled model's whole parameter tree from ``seed``, made on the
    device in one jitted call, in the plan's parameter type."""
    dtype = cm.plan.prec.param_dtype
    layout = _layout(cm)

    def make(key):
        tree: Dict[str, Dict[str, Any]] = {}
        for ukey, pkey, names, shape in layout:
            rule = _rule(pkey.split(":")[-1])
            hashes = jnp.asarray([name_hash(n) for n in names], jnp.int32)
            if ":" in pkey:     # folded: one slice per layer, stacked
                arr = lax.map(lambda h: _draw(jax.random.fold_in(key, h),
                                              rule, shape).astype(dtype),
                              hashes)
            else:
                arr = _draw(jax.random.fold_in(key, hashes[0]), rule,
                            shape).astype(dtype)
            tree.setdefault(ukey, {})[pkey] = arr
        return tree

    return jax.jit(make)(root_key(seed))
