"""Time one layer's routed expert MLP alone, on the chip: the dropless
grouped dispatch against dense-combine at the five routed configurations'
widths and pad shares, and a decode step's live path against dense-combine
by the rows that are live.

    python tools/profile_grouped_moe.py [--configs mixtral-8x7b-8l ...]
        [--rows 1 2] [--valid 0.25 0.75 1.0] [--row-tile 128]
        [--blocks 2048 2048] [--dense 1] [--layers 2] [--stacks whole]
    python tools/profile_grouped_moe.py --decode-rows 16 32
        --live-rows 2 4 8 16 32 [--configs ...] [--layers 2] [--ops 8]

A case is a prefill dispatch of ``rows`` prompts padded to the
configuration's width (its ``prefill_chunk_tokens``), the leading ``valid``
share of each row real tokens, through ``layers`` expert layers in one
``lax.scan`` over stacked int8 weights (the served form: the kernel reads a
layer's weights out of the stack, as under ``models/llama.py``). Seeded
weights and a seeded router, so routing is near uniform, as it is under the
benchmark's seeded weights. Device time is summed from a profiler trace
(``utils/xplane.py``), never a host clock, and given a LAYER.

A JSON line a case: ``grouped_ms`` (every operation of the grouped form: the
sort, the gathers, the three kernel calls, the combine), ``kernel_ms`` (the
three ``moe_grouped_matmul`` calls), ``dense_ms`` (dense-combine over the
same tokens; ``--dense 0`` leaves it out), the largest operations, what
``dispatch_path`` would pick at this tile, and the largest difference
between the two forms' results. The shared experts run the same in both and
are left out.

``--decode-rows`` times a DECODE step instead: a dispatch of that many
slots x 1 token of which the leading ``--live-rows`` hold a request (the
others dead, ``valid`` false), through the live path
(``ops/moe.py:moe_mlp_live``) and through dense-combine. Its line gives
``live_ms``, ``kernel_ms`` and ``dense_ms`` a layer, the experts the live
rows picked (``experts_live``, the mean over the layers, of ``held``), the
int8 bytes of those experts' matrices over ``kernel_ms`` (``kernel_gb_s``)
and of every held expert's over ``dense_ms`` (``dense_gb_s``), and the
operations around the kernel's calls (``around_ops``, ``around_ms``).

Import the package from another checkout with ``PYTHONPATH=<root>`` to time
that checkout on the same chip.
"""
import argparse
import json
import os
import sys
import tempfile
from unittest import mock

sys.path.append(os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.ops import moe
from distributed_llm_inference_tpu.ops.quant import QuantizedTensor
from distributed_llm_inference_tpu.utils.xplane import aggregate, find_xplane

KERNEL = "moe_grouped_matmul"
# configuration -> the widths its cell runs (benchmark/configs/<name>.json)
SHAPES = {
    "mixtral-8x7b-8l": dict(
        hidden=4096, ffn=14336, experts=8, k=2, shares=1, width=2048,
        scoring="softmax"),
    "moonlight-16b-a3b": dict(
        hidden=2048, ffn=1408, experts=64, k=6, shares=1, width=2048,
        scoring="sigmoid"),
    "keye-vl2-30b-a3b": dict(
        hidden=2048, ffn=768, experts=128, k=8, shares=1, width=4096,
        scoring="softmax"),
    "k-exaone-236b-a23b": dict(
        hidden=6144, ffn=2048, experts=128, k=8, shares=8, width=2048,
        scoring="sigmoid"),
    "glm-5.2": dict(
        hidden=6144, ffn=2048, experts=256, k=8, shares=16, width=2048,
        scoring="sigmoid"),
}


def model_config(shape) -> ModelConfig:
    return ModelConfig(
        vocab_size=128, hidden_size=shape["hidden"],
        intermediate_size=shape["ffn"], moe_intermediate_size=shape["ffn"],
        num_layers=1, num_heads=1, num_kv_heads=1, head_dim=128,
        num_experts=shape["experts"], num_experts_per_tok=shape["k"],
        expert_shares=shape["shares"], moe_scoring=shape["scoring"],
        family="mixtral",
    )


def seeded_layers(cfg, layers, key):
    """Stacked int8 expert weights and a bf16 router, made on the device."""
    held, h, f = cfg.num_held_experts, cfg.hidden_size, cfg.moe_intermediate_size

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate((
            ("we_g", (layers, held, h, f)), ("we_u", (layers, held, h, f)),
            ("we_d", (layers, held, f, h)),
        )):
            kq, ks = jax.random.split(jax.random.fold_in(key, i))
            out[name] = QuantizedTensor(
                q=jax.random.randint(kq, shape, -127, 128, jnp.int8),
                scale=jax.random.uniform(
                    ks, shape[:2] + shape[3:], jnp.float32, 0.5, 1.5
                ).astype(jnp.bfloat16) * (0.3 / 127 / shape[2] ** 0.5),
            )
        # logits of order one: a sigmoid router's scores must not saturate
        # into ties (top-k breaks a tie by index: a few experts for all)
        out["router"] = (jax.random.normal(
            jax.random.fold_in(key, 9), (layers, h, cfg.num_experts),
            jnp.float32,
        ) * h ** -0.5).astype(jnp.bfloat16)
        return out

    return make(key)


def traced(fn, *args, reps=2):
    """(result, operation -> ns a call of ``fn``, operation -> events)."""
    out = jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
        agg = aggregate(find_xplane(td))
    return out, {k: v / reps for k, v in agg["ops_ns"].items()}


def decode_cases(args, name, cfg, p, live, dense):
    """A line a (slots, live rows) decode step: the live path beside
    dense-combine, a layer."""
    held, h, f = cfg.num_held_experts, cfg.hidden_size, cfg.moe_intermediate_size
    layer_bytes = 3 * h * f       # one expert's int8 matrices
    per_layer = lambda ns: ns / args.layers / 1e6
    # bytes a layer over ns a layer is GB/s
    gb_s = lambda b, ns: round(b * args.layers / ns, 1) if ns else None

    @jax.jit
    def picked(p, x, valid):
        """Held experts the valid rows pick in each layer (the first
        layer's input: the count the routing's near-uniformity gives)."""
        return jnp.stack([
            jnp.sum(moe._combine_matrix(cfg, x, p["router"][i], None, valid)[1])
            for i in range(args.layers)
        ])

    for rows in args.decode_rows:
        x = jax.random.normal(
            jax.random.PRNGKey(args.seed + 1), (rows, 1, h), jnp.bfloat16
        )
        for alive in args.live_rows:
            if alive > rows:
                continue
            valid = (jnp.arange(rows) < alive)[:, None]
            got, ops = traced(live, p, x, valid)
            want, dops = traced(dense, p, x, valid)
            kernel = sum(v for k, v in ops.items() if KERNEL in k)
            around = {k: v for k, v in ops.items() if KERNEL not in k}
            experts = float(np.mean(np.asarray(picked(p, x, valid))))
            keep = np.asarray(valid)[..., None]
            a = np.where(keep, np.asarray(got, np.float32), 0)
            b = np.where(keep, np.asarray(want, np.float32), 0)
            print(json.dumps({
                "config": name, "decode_rows": rows, "live_rows": alive,
                "held": held, "experts": cfg.num_experts,
                "k": cfg.num_experts_per_tok, "H": h, "F": f,
                "blocks": args.blocks, "layers": args.layers,
                "rule": moe.dispatch_path(cfg, rows, 1),
                "experts_live": round(experts, 2),
                "live_ms": round(per_layer(sum(ops.values())), 4),
                "kernel_ms": round(per_layer(kernel), 4),
                "dense_ms": round(per_layer(sum(dops.values())), 4),
                "kernel_gb_s": gb_s(experts * layer_bytes, kernel),
                "dense_gb_s": gb_s(held * layer_bytes, sum(dops.values())),
                "around_ops": len(around),
                "around_ms": round(per_layer(sum(around.values())), 4),
                "top_ops_ms": [
                    [k, round(per_layer(v), 4)]
                    for k, v in sorted(ops.items(), key=lambda kv: -kv[1])
                ][:args.ops],
                "dense_top_ops_ms": [
                    [k, round(per_layer(v), 4)]
                    for k, v in sorted(dops.items(), key=lambda kv: -kv[1])
                ][:args.ops],
                "max_abs_diff": float(np.abs(a - b).max()),
                "ref_abs_max": float(np.abs(b).max()),
                "device": jax.devices()[0].device_kind,
            }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="+", default=sorted(SHAPES),
                    choices=sorted(SHAPES))
    ap.add_argument("--rows", nargs="+", type=int, default=[1])
    ap.add_argument("--valid", nargs="+", type=float, default=[0.25, 0.75, 1.0])
    ap.add_argument("--row-tile", type=int, default=moe.ROW_TILE)
    ap.add_argument("--blocks", nargs=2, type=int, default=list(moe.WEIGHT_BLOCK))
    ap.add_argument("--dense", type=int, default=1)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--stacks", choices=["whole", "sliced"], default="whole")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-rows", nargs="+", type=int, default=[],
                    help="time a decode step of this many slots instead")
    ap.add_argument("--live-rows", nargs="+", type=int, default=[2, 4, 8, 16, 32])
    ap.add_argument("--ops", type=int, default=8,
                    help="a decode line's largest operations, this many")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("a device time comes from a chip: no TPU here")

    def stack(cfg, path):
        """``layers`` routed MLPs, residual, by ``path``. The grouped and
        the live form take their expert stacks whole and name the layer to
        the kernel, as ``models/llama.py:block_apply`` does (``--stacks
        sliced``: a slice a scan step, which copies a layer's experts
        before each call)."""
        def run(p, x, valid):
            whole = {
                k: p[k] for k in moe.GROUPED_STACKS
                if path != "dense" and args.stacks == "whole"
            }
            scanned = {k: v for k, v in p.items() if k not in whole}

            def layer(x, xs):
                lp, i = xs
                lp = {**lp, **{k: moe.LayerOf(v, i) for k, v in whole.items()}}
                if path == "grouped":
                    y = moe.moe_mlp_grouped(
                        cfg, lp, x, valid, row_tile=args.row_tile,
                        blocks=tuple(args.blocks),
                    )
                elif path == "live":
                    y = moe.moe_mlp_live(
                        cfg, lp, x, valid, blocks=tuple(args.blocks)
                    )
                else:
                    with mock.patch.object(
                        moe, "dispatch_path", lambda *a, **k: "dense"
                    ):
                        y = moe.moe_mlp(cfg, lp, x, valid)
                return x + y, y

            return jax.lax.scan(
                layer, x, (scanned, jnp.arange(args.layers))
            )[1][0]

        return jax.jit(run)

    for name in args.configs:
        shape = SHAPES[name]
        cfg = model_config(shape)
        p = seeded_layers(cfg, args.layers, jax.random.PRNGKey(args.seed))
        grouped, dense = stack(cfg, "grouped"), stack(cfg, "dense")
        if args.decode_rows:
            decode_cases(args, name, cfg, p, stack(cfg, "live"), dense)
            del p
            continue
        for rows in args.rows:
            x = jax.random.normal(
                jax.random.PRNGKey(args.seed + 1),
                (rows, shape["width"], shape["hidden"]), jnp.bfloat16,
            )
            for share in args.valid:
                valid = jnp.broadcast_to(
                    jnp.arange(shape["width"]) < int(share * shape["width"]),
                    x.shape[:2],
                )
                with mock.patch.object(moe, "ROW_TILE", args.row_tile):
                    rule = moe.dispatch_path(cfg, rows, shape["width"])
                got, ops = traced(grouped, p, x, valid)
                line = {
                    "config": name, "rows": rows, "width": shape["width"],
                    "valid_share": share, "held": cfg.num_held_experts,
                    "experts": cfg.num_experts, "k": shape["k"],
                    "H": shape["hidden"], "F": shape["ffn"],
                    "row_tile": args.row_tile, "blocks": args.blocks,
                    "layers": args.layers, "stacks": args.stacks,
                    "rule": rule,
                    "top_ops_ms": [
                        [k, round(v / args.layers / 1e6, 4)]
                        for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:8]
                    ],
                    "grouped_ms": round(
                        sum(ops.values()) / args.layers / 1e6, 4),
                    "kernel_ms": round(sum(
                        v for k, v in ops.items() if KERNEL in k
                    ) / args.layers / 1e6, 4),
                }
                if args.dense:
                    want, dops = traced(dense, p, x, valid)
                    keep = np.asarray(valid)[..., None]
                    a = np.where(keep, np.asarray(got, np.float32), 0)
                    b = np.where(keep, np.asarray(want, np.float32), 0)
                    line.update(
                        dense_ms=round(
                            sum(dops.values()) / args.layers / 1e6, 4),
                        max_abs_diff=float(np.abs(a - b).max()),
                        ref_abs_max=float(np.abs(b).max()),
                    )
                line["device"] = jax.devices()[0].device_kind
                print(json.dumps(line), flush=True)
        del p


if __name__ == "__main__":
    main()
