"""How close the served SELECTION comes to the reference's, on the chip.

    chiprun --timeout 3000 -- python benchmarks/dsa_selection_check.py
        [--config perfbench/configs/glm-5.2-l5e16.json |
                  perfbench/configs/keye-vl-2.0-30b-a3b-l12e32.json] [--cpu]

``correct`` compares log-probabilities; what a learned sparse attention
adds to the comparison is a discrete choice (``index_topk`` of a
context), which bf16 activations move near the threshold.  This runs the
configuration's three reference prompts through the program's own
forwards on the chip (the whole-prompt pass, then four decode steps,
bf16 weights drawn by the program's recipe, the Pallas kernels: what the
server runs, without the gateway), takes out of them the selection of
every (position, picking layer) (a prompt pass's mask, a decode step's
positions), and starts the configuration's plain reference on the CPU
for the same sequences (float32, exact top-k).  It prints one JSON
object: the log-probability readings as ``perfbench/check.py`` takes
them, and per prompt and picking layer, over the rows past the pick
(positions from ``index_topk`` on: below it everything is picked), the
share whose served set equals the reference's exactly, and the mean
overlap and the mean count of rows that changed sides where it does not.  It writes the job
(sequences, top ids, served values) to ``chiprun_out/dsa_selection/``,
so that the reference at another precision can be run against the same
served values off the chip (``--job``, with ``--round float8`` for
weights rounded through float8 e4m3).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "dsa_selection")


def reference_side(cfg_path: str, job_path: str, out_path: str,
                   rounding: str, module: str = "") -> None:
    """The CPU process: the reference's log-probabilities of the job's
    top ids and its selections in the picking layers (packed bits)."""
    import jax.numpy as jnp
    import numpy as np

    import importlib

    with open(cfg_path) as fh:
        cfg = json.load(fh)
    # the configuration's own reference (GLM-5.2's, Keye-VL-2.0's): each
    # has draw_layer, draw_ends, picks and logprobs(selections=)
    ref = importlib.import_module(module or cfg["reference"]["module"])
    with open(job_path) as fh:
        job = json.load(fh)
    if rounding == "float8":
        f8 = lambda t: t.astype(jnp.float8_e4m3fn)
        draw_layer, draw_ends = ref.draw_layer, ref.draw_ends
        ref.draw_layer = lambda *a, **k: {
            n: (t if n == "router_bias" else f8(t).astype(t.dtype))
            for n, t in draw_layer(*a, **k).items()}
        ref.draw_ends = lambda *a, **k: {
            n: f8(t).astype(t.dtype) for n, t in draw_ends(*a, **k).items()}
    picked: list = []
    dtype = (jnp.float32 if cfg.get("torch_dtype") == "float32"
             else jnp.bfloat16)  # the type the program holds them in
    lps = ref.logprobs(cfg, 0, dtype, job["sequences"], job["first"],
                       selections=picked)
    layers = [i for i in range(cfg["num_hidden_layers"]) if ref.picks(cfg, i)]
    np.savez_compressed(
        out_path,
        **{f"sel_{s}_{i}": np.packbits(picked[i][s])
           for s in range(len(job["sequences"])) for i in layers},
        **{f"lp_{s}": np.asarray(
            [[lp[pos, t] for t in ids] for pos, ids in enumerate(tops)])
           for s, (lp, tops) in enumerate(zip(lps, job["top_ids"]))})


def readings(served, expected):
    diffs = [abs(a - b) for s, e in zip(served, expected)
             for sr, er in zip(s, e) for a, b in zip(sr, er)]
    return {"max_abs_diff": max(diffs), "mean_abs_diff": sum(diffs) / len(diffs),
            "compared": len(diffs)}


def serve_side(cfg_path: str, cpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import check
    from vgate_tpu.models import decoder, hybrid, specs
    from vgate_tpu.ops import dsa
    from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

    with open(cfg_path) as fh:
        cfg = json.load(fh)
    cfg["_path"] = cfg_path
    program = cfg["rehearse" if cpu else "program"]
    spec = dataclasses.replace(
        specs.spec_for_model_id(program["preset"]), name="selection-check",
        **program["overrides"])
    dtype = jnp.float32 if cpu else jnp.bfloat16
    params = decoder.init_params(spec, jax.random.PRNGKey(0), dtype)
    ps = 16 if cpu else 32
    steps = cfg["reference"]["decode_tokens"] - 1
    top = cfg["reference"]["top_logprobs"]
    buckets = (cfg["rehearse"]["program_defaults"] if cpu
               else cfg["program_defaults"])["prefill_buckets"]
    prompts = [[check.BYTE_OFFSET + b for b in
                ("User: " + text + "\nAssistant:").encode()]
               for text in check.prompts(cfg)]
    longest = max(len(p) for p in prompts) + steps + 1
    n_pages = min(b for b in buckets if b >= longest) // ps
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=n_pages + 1, page_size=ps,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=n_pages * ps, dtype_bytes=jnp.dtype(dtype).itemsize,
        pools=spec.kv_pools, index_layers=spec.index_layers,
        index_dim=spec.index_key_lanes)
    # what the forwards picked, taken out of the traced programs
    masks, picks = [], []
    select, positions = hybrid._dsa_prompt_select, dsa.select_positions

    def prompt_select(*args, **kw):
        mask = select(*args, **kw)
        jax.debug.callback(lambda m: masks.append(np.asarray(m[0]) != 0),
                           mask, ordered=True)
        return mask

    def select_positions(scores, k):
        sel = positions(scores, k)
        jax.debug.callback(lambda s: picks.append(np.asarray(s[0])), sel,
                           ordered=True)
        return sel

    hybrid._dsa_prompt_select = prompt_select
    dsa.select_positions = select_positions
    prefill = jax.jit(decoder.prefill_forward, static_argnums=1,
                      static_argnames=("use_pallas",), donate_argnums=(4, 5))
    decode = jax.jit(decoder.decode_forward, static_argnums=1,
                     static_argnames=("use_pallas",), donate_argnums=(4, 5))
    tables = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    job = {"weights_seed": 0, "sequences": [], "first": [], "top_ids": [],
           "served": []}
    served_sel = []  # per prompt: {layer: {position: set of positions}}
    layers = [i for i in range(spec.num_layers)
              if spec.stack[i][0] == "dsa"]
    for ids in prompts:
        n = len(ids)
        S = min(b for b in buckets if b >= n)
        toks = np.zeros((1, S), np.int32)
        toks[0, :n] = ids
        kp, vp = make_kv_buffers(geo, dtype)
        del masks[:], picks[:]
        logits, kp, vp, _ = prefill(
            params, spec, jnp.asarray(toks), jnp.asarray([n]), kp, vp,
            tables[:, :S // ps], use_pallas=not cpu,
            slots=jnp.asarray([0]))
        seq, tops, vals = list(ids), [], []
        sel = {i: {} for i in layers}
        for step in range(steps + 1):
            lp = jax.nn.log_softmax(logits[0].astype(jnp.float32))
            v, t = jax.lax.top_k(lp, top)
            tops.append([int(x) for x in t])
            vals.append([float(x) for x in v])
            seq.append(tops[-1][0])
            if step == steps:
                break
            logits, kp, vp, _, _ = decode(
                params, spec, jnp.asarray(seq[-1:]),
                jnp.asarray([len(seq) - 1]), kp, vp, tables,
                active=jnp.asarray([True]), use_pallas=not cpu)
        jax.effects_barrier()
        # the prompt pass's masks (one a picking layer, in stack order;
        # none where the bucket is no longer than the pick)
        if S > spec.index_topk:
            assert len(masks) == len(layers), len(masks)
            for i, mask in zip(layers, masks):
                for t_ in range(n):
                    sel[i][t_] = mask[t_, :t_ + 1]
        if n_pages * ps > spec.index_topk and picks:
            per_step = len(picks) // steps
            assert per_step == len(layers), (len(picks), steps)
            for step in range(steps):
                t_ = n + step
                for i, got in zip(layers, picks[step * per_step:]):
                    row = np.zeros(t_ + 1, bool)
                    row[got[:min(t_ + 1, spec.index_topk)]] = True
                    sel[i][t_] = row
        served_sel.append(sel)
        job["sequences"].append(seq)
        job["first"].append(n)
        job["top_ids"].append(tops)
        job["served"].append(vals)
    os.makedirs(OUT, exist_ok=True)
    job_path = os.path.join(OUT, "job.json")
    with open(job_path, "w") as fh:
        json.dump(job, fh)
    ref_cfg = cfg_path
    if cpu:  # the tiny preset's sizes
        ref_cfg = os.path.join(OUT, "tiny.json")
        with open(ref_cfg, "w") as fh:
            json.dump({"name": cfg["name"], **cfg["rehearse"]["model"]}, fh)
    out_path = os.path.join(OUT, "reference.npz")
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--job", job_path,
         "--config", ref_cfg, "--out", out_path,
         "--module", cfg["reference"]["module"]],
        check=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    want = np.load(out_path)
    expected = [want[f"lp_{s}"].tolist() for s in range(len(prompts))]
    result = {"config": cfg["name"], "device": jax.devices()[0].device_kind,
              "prompt_tokens": job["first"],
              "logprobs": readings(job["served"], expected), "selection": []}
    for s, (sel, seq) in enumerate(zip(served_sel, job["sequences"])):
        L = len(seq)
        for i in layers:
            ref_mask = np.unpackbits(want[f"sel_{s}_{i}"])[:L * L].reshape(
                L, L).astype(bool)
            # rows past the pick alone: below it everything is picked
            rows = sorted(t_ for t_ in sel[i] if t_ >= spec.index_topk)
            if not rows:
                continue
            same, overlaps, moved = 0, [], []
            for t_ in rows:
                a, b = sel[i][t_], ref_mask[t_, :t_ + 1]
                if np.array_equal(a, b):
                    same += 1
                else:
                    overlaps.append((a & b).sum() / max(1, b.sum()))
                    moved.append(int((a & ~b).sum()))
            result["selection"].append({
                "prompt": s, "tokens": job["first"][s], "layer": i,
                "rows_past_the_pick": len(rows),
                "exact_share": same / len(rows),
                "mean_overlap_where_not": (
                    float(np.mean(overlaps)) if overlaps else None),
                "mean_rows_changed_sides": (
                    float(np.mean(moved)) if moved else None),
                "max_rows_changed_sides": max(moved, default=0)})
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        ROOT, "perfbench", "configs", "glm-5.2-l5e16.json"))
    ap.add_argument("--cpu", action="store_true",
                    help="the tiny preset on the CPU: checks this script")
    ap.add_argument("--job", help="reference side: the job to compute")
    ap.add_argument("--out")
    ap.add_argument("--round", default="", choices=("", "float8"))
    ap.add_argument("--module", default="",
                    help="reference side: its module, where --config is "
                    "the tiny preset's sizes and names none")
    args = ap.parse_args()
    if args.job:
        reference_side(args.config, args.job, args.out, args.round,
                       args.module)
        if args.round:  # the reading against the served values
            import numpy as np

            with open(args.job) as fh:
                job = json.load(fh)
            want = np.load(args.out)
            expected = [want[f"lp_{s}"].tolist()
                        for s in range(len(job["sequences"]))]
            print(json.dumps({"rounded": args.round,
                              **readings(job["served"], expected)}))
        return 0
    print(json.dumps(serve_side(args.config, args.cpu)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
