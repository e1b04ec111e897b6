"""``genomes1k.chr22``: the 1000 Genomes workflow's data, step bodies and
plain reference.

Imports nothing of the program.  The step bodies are the user's code that
the SWIRL plan runs (static-shape ``jax.numpy``, one jitted program each);
the reference computes the same results directly, in exact integer
arithmetic and by other operations, with no workflow in between.

Step bodies, for ``n`` individuals blocks and ``m`` populations:

* ``sI_i``  — ``d0_i`` (the genotypes of the i-th range of the VCF's sites,
  every individual) → ``dI_i``: 0/1 mutation marks;
* ``sIM``   — ``dI_1..n`` → ``d^IM``: the ranges stacked, every site's marks;
* ``sSF``   — ``d0_SF`` (SIFT scores) → ``d^SF``: the sifted sites, ascending;
* ``sMO_h`` — ``d^IM``, ``d^SF``, ``dP_h`` (the population's individuals)
  → the population's pairwise overlap counts over the sifted sites;
* ``sF_h``  — ``d^IM``, ``dP_h`` → the population's carrier count at every
  site, after a histogram of those counts by frequency.

A sink step's output datum is named ``d^out_<step>``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SUBSET_OF = {"GBR": "EUR"}  # GBR's individuals are drawn from EUR's
SELECT_COLS = 128  # one lane tile: the selector of a carrier count


def out_name(step: str) -> str:
    return f"d^out_{step}"


def _key(seed: int) -> np.ndarray:
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def block_sites(cfg: dict) -> int:
    """Sites in each individuals step's range of the VCF's lines."""
    n = cfg["dag"]["n"]
    if cfg["sites"] % n:
        raise ValueError("sites must split evenly over the individuals steps")
    return cfg["sites"] // n


# ---------------------------------------------------------------------------
# Data, made on the device from the seed
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 1, 2))
def _genotype_block(rows: int, individuals: int, sites: int, key_data, block):
    """Genotypes of sites ``[block * rows, (block + 1) * rows)``."""
    key = jax.random.wrap_key_data(key_data)
    u = jax.random.uniform(jax.random.fold_in(key, 0), (sites,))
    u = jax.lax.dynamic_slice_in_dim(u, block * rows, rows)
    thresh = (0.5 * u**4 * 65536.0).astype(jnp.uint32)  # skewed frequencies
    bits = jax.random.bits(
        jax.random.fold_in(jax.random.fold_in(key, 1), block), (2, rows, individuals),
        jnp.uint16,
    ).astype(jnp.uint32)
    t = thresh[:, None]
    return (bits[0] < t).astype(jnp.int8) + (bits[1] < t).astype(jnp.int8)


@partial(jax.jit, static_argnums=(0, 1))
def _variant(sites: int, individuals: int, key_data, v):
    """SIFT scores and population member lists of one instance variant."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.wrap_key_data(key_data), 2), v)
    ks, kp = jax.random.split(key)
    sift = (jax.random.permutation(ks, sites).astype(jnp.float32) + 0.5) / sites
    perm = jax.random.permutation(kp, individuals).astype(jnp.int32)
    return sift, perm


@dataclass
class Data:
    blocks: list  # n ranges of the VCF's lines, int8 [sites / n, individuals]
    sift: list  # per variant, float32 [sites]
    pops: list  # per variant, one sorted int32 member list per population


def populations(cfg: dict, perm: np.ndarray) -> list[np.ndarray]:
    """Member lists in the configuration's order, from a permutation."""
    sizes = dict(cfg["populations"])
    start, members = 0, {}
    for name, size in cfg["populations"]:
        if name == "ALL" or name in SUBSET_OF:
            continue
        members[name] = perm[start : start + size]
        start += size
    if start != cfg["individuals"]:
        raise ValueError("the super-populations must cover every individual")
    for name, parent in SUBSET_OF.items():
        if name in sizes:
            members[name] = members[parent][: sizes[name]]
    members["ALL"] = np.arange(cfg["individuals"], dtype=np.int32)
    return [np.sort(members[name]) for name, _ in cfg["populations"]]


def make_data(cfg: dict, seed: int) -> Data:
    """The chromosome and the instance variants, block by block."""
    key = _key(seed)
    rows, sites = block_sites(cfg), cfg["sites"]
    blocks = []
    for b in range(cfg["dag"]["n"]):
        blocks.append(_genotype_block(rows, cfg["individuals"], sites, key, b))
        blocks[-1].block_until_ready()  # one block's transients at a time
    sift, pops = [], []
    for v in range(cfg["variants"]):
        s, perm = _variant(sites, cfg["individuals"], key, v)
        sift.append(s)
        pops.append([jnp.asarray(p) for p in populations(cfg, np.asarray(perm))])
    return Data(blocks=blocks, sift=sift, pops=pops)


def initial_payloads(cfg: dict, data: Data, v: int) -> dict:
    """The data the source location holds for an instance of variant ``v``."""
    out = {f"d0_{i + 1}": b for i, b in enumerate(data.blocks)}
    out["d0_SF"] = data.sift[v]
    out.update({f"dP_{h + 1}": p for h, p in enumerate(data.pops[v])})
    return out


# ---------------------------------------------------------------------------
# Step bodies
# ---------------------------------------------------------------------------


@jax.jit
def _mark(genotypes):
    return (genotypes != 0).astype(jnp.int8)


@jax.jit
def _merge(blocks):
    return jnp.concatenate(blocks, axis=0)


@partial(jax.jit, static_argnums=0)
def _sift(count: int, scores):
    return jnp.sort(jnp.argsort(scores)[:count]).astype(jnp.int32)


@jax.jit
def _overlap(marks, sites, members):
    # The sifted sites' rows, turned so that each member's marks are a row.
    x = jnp.take(jnp.take(marks, sites, axis=0).T, members, axis=0)
    x = x.astype(jnp.bfloat16)
    return jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


@partial(jax.jit, static_argnums=0)
def _frequency(bins: int, marks, members):
    # Carrier counts as one matrix product, the marks against a selector
    # whose first column flags the members; exact, as counts stay under 2^24.
    select = jnp.zeros((marks.shape[1], SELECT_COLS), jnp.bfloat16).at[members, 0].set(1)
    counts = jax.lax.dot_general(
        marks.astype(jnp.bfloat16), select, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0].astype(jnp.int32)
    which = jnp.minimum(counts * bins // members.shape[0], bins - 1)
    hist = jnp.sum(which[:, None] == jnp.arange(bins)[None, :], axis=0, dtype=jnp.int32)
    return jnp.concatenate([hist, counts])


def steps(cfg: dict, current) -> dict:
    """Step name → body; ``current()`` gives the running instance's inputs."""
    n, m = cfg["dag"]["n"], cfg["dag"]["m"]
    fns = {"s0": lambda inputs: current()}
    for i in range(1, n + 1):
        fns[f"sI_{i}"] = lambda inputs, i=i: {f"dI_{i}": _mark(inputs[f"d0_{i}"])}
    fns["sIM"] = lambda inputs: {
        "d^IM": _merge([inputs[f"dI_{i}"] for i in range(1, n + 1)])
    }
    fns["sSF"] = lambda inputs: {"d^SF": _sift(cfg["sifted_sites"], inputs["d0_SF"])}
    for h in range(1, m + 1):
        fns[f"sMO_{h}"] = lambda inputs, h=h: {
            out_name(f"sMO_{h}"): _overlap(inputs["d^IM"], inputs["d^SF"], inputs[f"dP_{h}"])
        }
        fns[f"sF_{h}"] = lambda inputs, h=h: {
            out_name(f"sF_{h}"): _frequency(
                cfg["frequency_bins"], inputs["d^IM"], inputs[f"dP_{h}"]
            )
        }
    return fns


# ---------------------------------------------------------------------------
# Least work of each step, for the roofline share
# ---------------------------------------------------------------------------


def step_costs(cfg: dict) -> dict[str, tuple[float, float]]:
    """Step → (matrix-unit FLOPs, least bytes read and written).

    The bytes count each input element the step needs once and each output
    element once, so no schedule can move fewer; sorting and elementwise
    work are not counted as FLOPs.
    """
    n, rows, s = cfg["dag"]["n"], block_sites(cfg), cfg["sites"]
    k, bins, ind = cfg["sifted_sites"], cfg["frequency_bins"], cfg["individuals"]
    costs = {"s0": (0.0, 0.0), "sIM": (0.0, 2.0 * ind * s), "sSF": (0.0, 4.0 * s + 4.0 * k)}
    for i in range(1, n + 1):
        costs[f"sI_{i}"] = (0.0, 2.0 * rows * ind)
    for h, (_, size) in enumerate(cfg["populations"], start=1):
        costs[f"sMO_{h}"] = (2.0 * size * size * k, size * k + 4.0 * (k + size + size * size))
        costs[f"sF_{h}"] = (0.0, size * s + 4.0 * (size + bins + s))
    return costs


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------


def reference(cfg: dict, data: Data, v: int, *, accumulate=jnp.int32) -> dict:
    """Every sink output of an instance of variant ``v``, computed directly.

    Integer arithmetic throughout.  The sifted sites are those scoring under
    ``sifted_sites / sites`` (the scores are a permutation of
    ``(j + 0.5) / sites``); counts accumulate in ``accumulate``.
    """
    m, bins = cfg["dag"]["m"], cfg["frequency_bins"]
    k, sites = cfg["sifted_sites"], cfg["sites"]
    marks = jnp.concatenate([b > 0 for b in data.blocks], axis=0).astype(jnp.int8)
    chosen = jnp.nonzero(data.sift[v] < k / sites, size=k)[0]
    low = accumulate != jnp.int32
    out = {}
    for h in range(1, m + 1):
        members = data.pops[v][h - 1]
        cols = marks[:, members]
        x = cols[chosen]
        if low:
            x = x.astype(accumulate)
        overlap = jax.lax.dot_general(
            x, x, (((0,), (0,)), ((), ())), preferred_element_type=accumulate
        )
        counts = np.asarray(
            jnp.sum(cols.astype(accumulate) if low else cols, axis=1, dtype=accumulate)
        ).astype(np.int64)
        which = np.minimum(counts * bins // members.shape[0], bins - 1)
        hist = np.bincount(which, minlength=bins)
        out[out_name(f"sMO_{h}")] = np.asarray(overlap).astype(np.float64)
        out[out_name(f"sF_{h}")] = np.concatenate([hist, counts]).astype(np.float64)
        del cols, x
    return out


def compare(cfg: dict, got: dict, want: dict) -> dict[str, float]:
    """Largest absolute gaps of the program's outputs from the reference's."""
    bins = cfg["frequency_bins"]
    gaps = {"overlap_gap": 0.0, "count_gap": 0.0, "histogram_gap": 0.0}
    for name, ref in want.items():
        mine = np.asarray(got[name]).astype(np.float64)
        if mine.shape != ref.shape:
            return {g: math.inf for g in gaps}
        diff = np.abs(mine - ref)
        if name.startswith(out_name("sMO_")):
            gaps["overlap_gap"] = max(gaps["overlap_gap"], float(diff.max()))
        else:
            gaps["histogram_gap"] = max(gaps["histogram_gap"], float(diff[:bins].max()))
            gaps["count_gap"] = max(gaps["count_gap"], float(diff[bins:].max()))
    return gaps
