#!/usr/bin/env python
"""Time an attention layer's causal core ALONE on the chip, at a sequence
cell's shape, for each candidate of ``backbone.MLA_BLOCK``: what chose the
constant (PERF.md section 3).

    python scripts/mla_core_chip.py [--workload glm-flash.build-horizons] \\
        [--blocks 256,512,1024,0] [--max-blocks 4,8,16] [--rows 512,256,128,64] \\
        [--repeats 5]

For every block size (0: one block, the whole square) it sets the constant,
compiles the core of the cell's attention layers (``backbone._causal_core``
where they are latent, ``backbone._grouped_core`` where they are grouped: the
preset is looked up by the configuration's ``kind``) at the cell's shape
(``mixer_group`` sequences of ``context`` rows, the configuration's heads and
widths, matmul operands bfloat16) as the forward alone and as the forward
with its ``jax.vjp`` for all its inputs, runs each ``--repeats`` times after a
warm-up and prints the best wall milliseconds: ``forward_ms``,
``forward_backward_ms`` and ``layer_ms``, their sum, which is what one
attention block of an optimiser step costs (``_mixer_bwd`` recomputes the
forward).  A preset with windowed layers (``afmoe``) has two cores, timed one
after the other: the windowed one (``core`` ``swa``) for every ``--rows``,
the candidates of ``backbone.WINDOW_ROWS`` (the rows a trip of its loop
takes), each with ``trip_score_bytes``, the float32 scores of one trip, which
``backbone.WINDOW_TRIP_BYTES`` bounds; and the whole-prefix one (``gqa``) for
every ``--max-blocks``, the candidates of ``backbone.ATTN_MAX_BLOCKS`` that
cut a sequence longer than ``MLA_BLOCK`` times it (8,192 rows: blocks of
2,048, 1,024, 512).  Chip only (exit 3 without one); leaves the compile cache
alone.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def config_of(model: dict):
    """The backbone's configuration for a configuration's ``model`` object:
    the preset of ``model["kind"]`` (widths it leaves out are its kind's),
    matmul operands bfloat16, as ``auto`` resolves on a TPU."""
    from gordo_tpu.models.factories import backbone

    known = {k: v for k, v in model.items()
             if k in backbone.BackboneConfig.__dataclass_fields__ and k != "compute_dtype"}
    return getattr(backbone, model["kind"])(1, 1, compute_dtype="bfloat16", **known).cfg


def core_shapes(model: dict):
    """``(cfg, cores, shapes)``: the backbone's configuration
    (:func:`config_of`), the causal cores of its attention layers by kind
    (``mla``; ``gqa``; ``swa`` and ``gqa`` where the pattern has both) and
    the shapes of a core's inputs for one mixer call of ``model``: ``(q, k_n,
    k_r, v)`` for latent attention, ``(q, k, v)`` for grouped."""
    from gordo_tpu.models.factories import backbone

    cfg = config_of(model)
    b, t, h = cfg.mixer_group, int(model["context"]), cfg.num_heads
    grouped = [kind for kind in ("swa", "gqa") if kind in cfg.pattern]
    if grouped:
        hd, kv = cfg.gqa_head_dim, cfg.num_kv_heads
        cores = {kind: functools.partial(
            backbone._grouped_core, window=cfg.attn_window if kind == "swa" else 0, prefix=kind)
            for kind in grouped}
        return cfg, cores, ((b, t, h, hd), (b, t, kv, hd), (b, t, kv, hd))
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    return cfg, {"mla": backbone._causal_core}, (
        (b, t, h, dn + dr), (b, t, h, dn), (b, t, dr), (b, t, h, cfg.v_head_dim))


def time_core(core, shapes, repeats: int, seed: int = 0):
    """``{"forward_ms", "forward_backward_ms", "layer_ms", "compile_s"}`` of
    ``core(*inputs)`` on seeded normal inputs of ``shapes``."""
    import jax
    import jax.numpy as jnp
    from fit_step_chip import timed

    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes) + 1)
    args = [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]
    ct = jax.random.normal(keys[-1], jax.eval_shape(core, *args).shape, jnp.float32)

    def both(*inputs):
        out, vjp = jax.vjp(core, *inputs)
        return out, vjp(ct)

    t0 = time.perf_counter()
    forward = jax.jit(core).lower(*args).compile()
    forward_backward = jax.jit(both).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    f, fb = (timed(c, args, repeats) * 1e3 for c in (forward, forward_backward))
    return {"forward_ms": f, "forward_backward_ms": fb, "layer_ms": f + fb,
            "compile_s": round(compile_s, 1)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="mla_core_chip")
    parser.add_argument("--workload", default="glm-flash.build-horizons")
    parser.add_argument("--blocks", default="256,512,1024,0")
    parser.add_argument("--max-blocks", default="4,8,16",
                        help="candidates of ATTN_MAX_BLOCKS, for a whole-prefix core "
                             "beside a windowed one")
    parser.add_argument("--rows", default="512,256,128,64",
                        help="candidates of WINDOW_ROWS, for a windowed core")
    parser.add_argument("--repeats", type=int, default=5)
    args = parser.parse_args(argv)
    import jax

    from benchmark import device, manifest as manifest_mod

    try:
        device.require_chips(1)
    except device.NoChip as e:
        print(json.dumps({"error": str(e)}))
        return 3
    jax.config.update("jax_enable_compilation_cache", False)
    from gordo_tpu.models.factories import backbone

    manifest = manifest_mod.Manifest()
    model = manifest.config(manifest.cell(args.workload)["config"])["model"]
    cfg, cores, shapes = core_shapes(model)
    block_rows, max_blocks = backbone.MLA_BLOCK, backbone.ATTN_MAX_BLOCKS
    for kind, core in cores.items():
        # beside a windowed core the whole-prefix one is cut by the number of
        # its blocks, at the block the module has; the windowed one by the
        # rows of a trip, the one candidate the rule is left with
        by_count = kind == "gqa" and "swa" in cores
        what, given = (("rows", args.rows) if kind == "swa" else
                       ("max_blocks", args.max_blocks) if by_count else ("block", args.blocks))
        for candidate in (int(c) for c in given.split(",")):
            line = {"workload": args.workload, "core": kind, what: candidate,
                    "device_kind": jax.devices()[0].device_kind,
                    "shape": [list(s) for s in shapes]}
            if kind == "swa":
                backbone.WINDOW_ROWS = (candidate,)
                b, _, h, _ = shapes[0]
                line["trip_score_bytes"] = backbone.trip_score_bytes(
                    cfg.attn_window, candidate, b * h)
            else:
                backbone.MLA_BLOCK = block_rows if by_count else candidate or shapes[0][1]
                backbone.ATTN_MAX_BLOCKS = candidate if by_count else max_blocks
            line.update(time_core(functools.partial(core, cfg), shapes, args.repeats))
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
