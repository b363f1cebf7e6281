"""The paged decode step moves no KV page pool as a whole.

Each decode step changes one row per slot of each layer's pool, and the
flash kernel DMAs only the pages it reads. The scan-stacked layers keep
their pools stacked (P, n_pages, ...) in the layer scan's carry
(models/lm.py ``_scan_blocks``) and address them by layer index; scanned
as ``xs``/``ys`` instead, every layer of every step would slice its pool
out and stack it back, two whole-pool copies, and a pool stored other
than as the kernel's blocks are cut would be relaid out before each call.
These tests compile ``decode_many`` for a paged Llama-style model with
three scan periods and look for any op that produces a pool-shaped array.

The XLA decode path is checked in the optimized HLO. The flash kernel in
interpret mode cannot be: the interpreter carries every operand through
its own loop and so copies the pool by construction. Its check reads the
traced program instead, everything outside the kernel; the compiled kernel
is checked in the optimized TPU HLO by tests/test_tpu_compile.py.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import LayerKind, ModelConfig
from repro.kernels.flash_decode import decode_kernel_override
from repro.models import api as model_api
from repro.models.common import PagedLayout

SLOTS, CACHE_LEN, PAGE = 4, 64, 8
N_PAGES = SLOTS * CACHE_LEN // PAGE + 3     # an odd count: no shape aliasing

#: ops that would move a whole pool (HLO opcode names)
POOL_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
              "dynamic-update-slice", "reshape")


def _cfg(mixer):
    extra = dict(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                 v_head_dim=16) if mixer == "mla" else {}
    return ModelConfig(
        arch=f"pool-{mixer}", family="dense", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4 if mixer == "mla" else 2, head_dim=16,
        d_ff=128, vocab_size=256, pattern=(LayerKind(mixer, "dense"),),
        dtype="float32", **extra)


def _setup(mixer):
    cfg = _cfg(mixer)
    params = model_api.init_model(jax.random.PRNGKey(0), cfg)
    cache = model_api.init_cache(params, cfg, SLOTS, CACHE_LEN,
                                 paged=PagedLayout(PAGE, N_PAGES))
    pools = [x for p, x in jax.tree_util.tree_leaves_with_path(cache)
             if str(getattr(p[-1], "key", "")).endswith("_pages")]
    assert pools and all(x.shape[:2] == (3, N_PAGES) for x in pools)
    shapes = {x.shape for x in pools} | {x.shape[1:] for x in pools}
    return cfg, params, cache, shapes


def _decode_many(cfg, kind):
    def fused(p, c, t, s):
        with decode_kernel_override(kind):
            return model_api.decode_many(p, c, cfg, t, s, 4)
    return jax.jit(fused, donate_argnums=(1,))


def _args(params, cache):
    return (params, cache, jnp.zeros((SLOTS, 1), jnp.int32),
            jnp.arange(SLOTS, dtype=jnp.int32))


def pool_moves_in_hlo(hlo_text, shapes):
    """(opcode, line) of every instruction in ``POOL_MOVES`` whose result
    (or a tuple part of it) has one of ``shapes``."""
    needles = ["[" + ",".join(map(str, s)) + "]" for s in shapes]
    found = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.-]+ = (.*?) ([a-z][\w-]*)\(", line)
        if m and m.group(2) in POOL_MOVES and any(
                n in m.group(1) for n in needles):
            found.append((m.group(2), line.strip()[:160]))
    return found


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def pool_moves_in_jaxpr(jaxpr, shapes):
    """Pool-shaped values a scan takes as ``xs`` or returns as ``ys``, and
    pool-shaped results of slicing, reshaping or copying ops, anywhere in
    ``jaxpr`` outside a ``pallas_call``."""
    moving = {"copy", "copy_p", "dynamic_slice", "dynamic_update_slice",
              "reshape", "squeeze", "slice", "gather", "transpose",
              "concatenate"}
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            continue
        if name == "scan":
            k = eqn.params["num_consts"] + eqn.params["num_carry"]
            scanned = (eqn.invars[k:] +
                       eqn.outvars[eqn.params["num_carry"]:])
            found += [("scan xs/ys", v.aval.shape) for v in scanned
                      if tuple(v.aval.shape) in shapes]
        if name in moving:
            found += [(name, v.aval.shape) for v in eqn.outvars
                      if tuple(v.aval.shape) in shapes]
        for sub in _subjaxprs(eqn):
            found += pool_moves_in_jaxpr(sub, shapes)
    return found


@pytest.mark.parametrize("mixer", ["attn", "mla"])
def test_xla_paged_decode_moves_no_pool(mixer):
    cfg, params, cache, shapes = _setup(mixer)
    hlo = _decode_many(cfg, "xla").lower(
        *_args(params, cache)).compile().as_text()
    assert not pool_moves_in_hlo(hlo, shapes), pool_moves_in_hlo(hlo, shapes)


@pytest.mark.parametrize("kind", ["xla", "flash"])
def test_paged_decode_program_moves_no_pool(kind):
    """The traced decode window, kernel aside: pools enter no scan as
    ``xs``, leave none as ``ys``, and no op slices, reshapes or copies
    one. ``flash`` runs the interpret-mode kernel here."""
    cfg, params, cache, shapes = _setup("attn")
    jaxpr = jax.make_jaxpr(_decode_many(cfg, kind))(*_args(params, cache))
    found = pool_moves_in_jaxpr(jaxpr.jaxpr, shapes)
    assert not found, found


def test_paged_decode_program_check_sees_a_scanned_pool():
    """The jaxpr check is not vacuous: the same stacked cache scanned as
    ``xs``/``ys`` -- the layout the carry replaces -- is reported."""
    _, _, cache, shapes = _setup("attn")

    def scanned(c):
        return jax.lax.scan(lambda h, x: (h, x), 0, c)
    found = pool_moves_in_jaxpr(jax.make_jaxpr(scanned)(cache).jaxpr, shapes)
    assert any(kind == "scan xs/ys" for kind, _ in found), found
