"""Per-phase cost breakdown of a flagship train step — and, with
`--serving` (ISSUE 11), of the serving engine loop.

VERDICT r3 Missing #4: no committed step-time breakdown existed, so nobody
could say whether the measured MFU was attention, input feed, launch
overhead, or missing fusion. This tool produces that evidence tier:

  python tools/step_breakdown.py [--model gpt|ernie] [--layers N]
      [--hidden H] [--batch B] [--seq S] [--out PERF_BREAKDOWN.md]

Serving mode (`--serving`): profile a ServingEngine loop instead of a
train step. Three arms of the same closed-batch GPT workload — s=1
(the per-token loop), s=8 half-duplex (PR 6 horizons, plan blocks on
drain), s=8 zero-bubble (pipelined + on-device early stop) — each
reporting the per-step wall-time split the engine's own instruments
measure: host planning (and how much of it ran OVERLAPPED under an
in-flight launch), blocking drain waits (the host-blocked-on-device
share), and launch/replay. The acceptance evidence is the UNOVERLAPPED
host-plan share at s=8 pipelined (< 5%), committed into
PERF_BREAKDOWN.md between the serving-breakdown sentinels (the train
table above it is left untouched).

Methodology
-----------
1. Build the flagship model + AdamW + `jit.TrainStep` on whatever backend
   is live (the TPU where the process has one; the XLA:CPU proxy otherwise
   — the HLO is the same module XLA compiles for TPU minus target-specific
   fusion choices, so the op-class shares are indicative, not
   authoritative; the backend is recorded in the output header).
2. Run one compile step + warmups, then trace `iters` steps under
   `jax.profiler.trace` (chrome trace committed next to the table).
3. Parse the trace's XLA device/host events and aggregate self-time into
   phases by HLO op-name patterns: attention (flash kernel / dot+softmax),
   other matmuls (qkv/mlp/head projections), embedding gathers, optimizer
   update (fused elementwise chains touching opt state), collectives,
   layernorm/elementwise, and everything else.
4. Emit a markdown table (share of step time per phase) + the raw trace
   path. Also prints XLA's static cost analysis (FLOPs, bytes accessed)
   for the step executable as a cross-check.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PHASES = [
    # (phase, substrings matched against HLO event names, lowercased).
    # ORDER MATTERS: collectives must match before the elementwise/gather
    # buckets ("all-reduce" contains "reduce", "all-gather" contains
    # "gather"), attention before matmul.
    ("collectives", ("all-reduce", "all-gather", "all-to-all",
                     "reduce-scatter", "collective", "psum",
                     "permute")),
    ("attention", ("flash", "attention", "softmax", "reduce-window",
                   "cumulative_logsumexp")),
    ("matmul/other", ("dot", "matmul", "einsum", "convolution")),
    ("embedding/gather", ("gather", "scatter", "dynamic-slice",
                          "dynamic_slice", "take")),
    ("optimizer/elementwise", ("adam", "multiply", "add", "subtract",
                               "divide", "sqrt", "rsqrt", "fused",
                               "loop_fusion", "input_fusion",
                               "output_fusion", "reduce", "select",
                               "compare", "exponential", "tanh", "rng")),
    ("copy/infeed", ("copy", "infeed", "outfeed", "transpose",
                     "bitcast", "broadcast", "reshape", "convert",
                     "slice", "concatenate", "pad")),
]

# host-side scaffolding lanes that would double-count the HLO spans they
# envelop (python frames, thunk executor, profiler wrappers)
_SCAFFOLD = ("$", "np.", "thunkexecutor", "profiler", "xlamodule",
             "pjrt", "execute", "buffer", "stream", "transferto",
             "programattributes")


def _is_hlo_event(name: str) -> bool:
    low = name.lower()
    return not any(low.startswith(s) or s in low for s in _SCAFFOLD)


def classify(name: str) -> str:
    low = name.lower()
    for phase, keys in PHASES:
        if any(k in low for k in keys):
            return phase
    return "other"


def run_and_trace(model: str, layers: int, hidden: int, batch: int,
                  seq: int, vocab: int, iters: int, trace_dir: str):
    import jax
    import numpy as np

    import paddle_tpu as paddle

    backend = jax.default_backend()
    paddle.seed(0)
    if model == "ernie":
        from paddle_tpu.models.ernie import (
            ErnieConfig, ErnieForPretraining, ernie_pretrain_loss_fn,
            mask_tokens,
        )

        cfg = ErnieConfig(vocab_size=vocab, hidden_size=hidden,
                          num_layers=layers,
                          num_heads=max(hidden // 64, 1),
                          max_position=seq, dropout=0.0)
        net = ErnieForPretraining(cfg)
        loss_fn = ernie_pretrain_loss_fn
        rng = np.random.default_rng(0)
        ids, labels = mask_tokens(rng.integers(5, vocab, (batch, seq)),
                                  vocab, rng)
        args = (paddle.to_tensor(ids), paddle.to_tensor(labels),
                paddle.to_tensor(rng.integers(0, 2, (batch,))))
    else:
        from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn

        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers,
                        num_heads=max(hidden // 64, 1), max_seq_len=seq,
                        dropout=0.0)
        net = GPT(cfg)
        loss_fn = gpt_loss_fn
        rng = np.random.default_rng(0)
        toks = paddle.to_tensor(rng.integers(0, vocab, (batch, seq)))
        args = (toks, toks)
    n_params = sum(p.size for p in net.parameters())
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=3e-4, weight_decay=0.1)
    step = paddle.jit.TrainStep(net, loss_fn, opt, amp_level="O1",
                                amp_dtype="bfloat16")
    float(step(*args))      # compile
    for _ in range(2):
        step(*args)
    float(step(*args))      # fence

    with jax.profiler.trace(trace_dir):
        t0 = time.time()
        for _ in range(iters):
            loss = step(*args)
        loss_v = float(loss)    # host readback fences the chain
        dt = (time.time() - t0) / iters
    return {"backend": backend, "params_m": n_params / 1e6,
            "step_ms": dt * 1e3, "loss": loss_v,
            "tokens_per_step": batch * seq, "model": model,
            "layers": layers, "hidden": hidden, "batch": batch,
            "seq": seq}


def parse_trace(trace_dir: str):
    """Aggregate device-lane event self-time by phase from the
    trace-viewer JSON(.gz) the profiler wrote."""
    paths = (glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True)
        + glob.glob(os.path.join(trace_dir, "**", "*.trace.json"),
                    recursive=True))
    if not paths:
        return None, None
    path = max(paths, key=os.path.getmtime)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # device lanes: process names containing TPU/device or XLA Ops threads
    pid_names = {e.get("pid"): str(e.get("args", {}).get("name", ""))
                 for e in events if e.get("name") == "process_name"}
    device_pids = {p for p, n in pid_names.items()
                   if any(s in n.lower() for s in ("tpu", "device", "xla"))}
    totals: dict = {}
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        name = str(e.get("name", ""))
        if not _is_hlo_event(name):
            continue
        phase = classify(name)
        totals[phase] = totals.get(phase, 0.0) + float(e["dur"])
    return totals, path


def emit_markdown(meta, totals, trace_path, out_path):
    lines = [
        "# Flagship step-time breakdown",
        "",
        f"Generated by `tools/step_breakdown.py` on backend "
        f"**{meta['backend']}**"
        + (" — CPU **proxy** numbers: op-class shares are indicative of "
           "the XLA module structure, NOT of TPU wall-clock (MXU/HBM "
           "ratios differ); regenerate on the chip"
           if meta["backend"] != "tpu" else " (real chip)"),
        "",
        f"- model: {meta['model']} {meta['layers']}L/{meta['hidden']}h, "
        f"batch {meta['batch']} x seq {meta['seq']} "
        f"({meta['params_m']:.1f}M params)",
        f"- step time: {meta['step_ms']:.1f} ms "
        f"({meta['tokens_per_step'] / meta['step_ms'] * 1000:.0f} "
        "tokens/s)",
        f"- loss (finite check): {meta['loss']:.4f}",
        f"- chrome trace: `{trace_path}`",
        "",
        "| phase | device self-time share |",
        "|---|---|",
    ]
    total = sum(totals.values()) or 1.0
    for phase, t in sorted(totals.items(), key=lambda kv: -kv[1]):
        lines.append(f"| {phase} | {t / total:.1%} |")
    lines += [
        "",
        "Phase = HLO-event-name classification "
        "(see PHASES in tools/step_breakdown.py). 'other' holds "
        "unmatched fusions; a large 'copy/infeed' share on TPU would "
        "point at layout/transfer problems, a large 'other' at missed "
        "fusion opportunities.",
    ]
    with open(out_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


SERVING_BEGIN = "<!-- serving-breakdown:begin -->"
SERVING_END = "<!-- serving-breakdown:end -->"


def run_serving(layers: int, hidden: int, batch: int, requests: int,
                prompt: int, gen: int, vocab: int):
    """Profile three serving-loop arms; returns (meta, arms). Each arm
    is the engine's own per-step instrument split: host planning
    (overlapped vs not), blocking drain waits, launch/replay = rest."""
    import time as _time

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.serving import GPTRunner, SamplingParams, ServingEngine

    backend = jax.default_backend()
    paddle.seed(0)
    max_len = prompt + gen
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=max(hidden // 64, 1),
                    max_seq_len=max_len, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    block_size = min(16, max_len)
    runner = GPTRunner(model, block_size=block_size, max_model_len=max_len)
    pages = -(-max_len // block_size)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, vocab, prompt))
               for _ in range(requests)]

    def arm(name, s, **kw):
        eng = ServingEngine(runner, num_blocks=batch * pages + 1,
                            max_batch_size=batch, max_model_len=max_len,
                            decode_horizon=s, **kw)
        t0 = _time.time()
        for i, p in enumerate(prompts):
            eng.add_request(p, SamplingParams(max_tokens=gen),
                            request_id=f"r{i}")
        eng.run()
        wall = _time.time() - t0
        m = eng.metrics.snapshot()
        step_s = m["step_seconds"] or 1e-9
        plan = m["host_plan_seconds"]
        over = m["overlapped_plan_seconds"]
        drain = m["drain_wait_seconds"]
        return {"arm": name, "s": s, "wall_s": wall,
                "tokens": m["tokens_generated"],
                "tokens_per_sec": m["tokens_generated"] / wall,
                "steps": m["decode_steps"],
                "step_seconds": step_s,
                "host_plan_share": plan / step_s,
                "host_plan_unoverlapped_share": (plan - over) / step_s,
                "drain_wait_share": drain / step_s,
                "launch_replay_share": max(0.0, (step_s - plan - drain)
                                           / step_s),
                "host_syncs_per_token": m["host_syncs_per_token"],
                "planned_ahead_steps": m["planned_ahead_steps"],
                "device_idle_fraction": m["device_idle_fraction"]}

    specs = [("s1_per_step", 1, {}),
             ("s8_half_duplex", 8, {}),
             ("s8_zero_bubble", 8, {"pipelined": True,
                                    "horizon_early_stop": True})]
    for name, s, kw in specs:            # warmup/compile pass
        arm(name, s, **kw)
    arms = [arm(name, s, **kw) for name, s, kw in specs]
    meta = {"backend": backend, "layers": layers, "hidden": hidden,
            "batch": batch, "requests": requests, "prompt": prompt,
            "gen": gen}
    return meta, arms


def emit_serving_markdown(meta, arms, out_path):
    """Write the serving-loop split between the sentinels in out_path,
    leaving everything else (the train-step table) untouched."""
    lines = [
        SERVING_BEGIN,
        "",
        "## Serving engine loop breakdown (ISSUE 11)",
        "",
        f"Generated by `tools/step_breakdown.py --serving` on backend "
        f"**{meta['backend']}**"
        + (" — CPU **proxy**: the 'device' computes on the same host "
           "cores, so wall-clock gains from overlap are muted; the "
           "SHARE split below is the structural evidence (on TPU the "
           "unoverlapped host share is device idle time)"
           if meta["backend"] != "tpu" else " (real chip)"),
        "",
        f"- workload: GPT {meta['layers']}L/{meta['hidden']}h, "
        f"batch {meta['batch']}, {meta['requests']} reqs x "
        f"{meta['prompt']}p+{meta['gen']}g tokens",
        "",
        "| arm | tok/s | syncs/token | host-plan | unoverlapped plan "
        "| drain wait | launch+replay | planned-ahead steps |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for a in arms:
        lines.append(
            f"| {a['arm']} | {a['tokens_per_sec']:.0f} | "
            f"{a['host_syncs_per_token']:.3f} | "
            f"{a['host_plan_share']:.1%} | "
            f"{a['host_plan_unoverlapped_share']:.1%} | "
            f"{a['drain_wait_share']:.1%} | "
            f"{a['launch_replay_share']:.1%} | "
            f"{a['planned_ahead_steps']:.0f} |")
    zb = arms[-1]
    verdict = ("MET" if zb["host_plan_unoverlapped_share"] < 0.05
               else "NOT MET (CPU-proxy caveat applies)")
    lines += [
        "",
        f"Acceptance: unoverlapped host-plan share at s=8 pipelined = "
        f"**{zb['host_plan_unoverlapped_share']:.2%}** (< 5% bar: "
        f"{verdict}). Shares are fractions of total step wall time, "
        "measured by the engine's own step/plan/drain instruments.",
        "",
        SERVING_END,
    ]
    block = "\n".join(lines)
    try:
        with open(out_path) as f:
            text = f.read()
    except FileNotFoundError:
        text = ""
    if SERVING_BEGIN in text and SERVING_END in text:
        pre = text.split(SERVING_BEGIN)[0]
        post = text.split(SERVING_END, 1)[1]
        text = pre + block + post
    else:
        text = text.rstrip() + "\n\n" + block + "\n"
    with open(out_path, "w") as f:
        f.write(text)
    print(block)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt", choices=("gpt", "ernie"))
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--trace-dir", default="perf_trace")
    ap.add_argument("--out", default="PERF_BREAKDOWN.md")
    ap.add_argument("--serving", action="store_true",
                    help="profile the serving engine loop instead of a "
                         "train step (ISSUE 11): s=1 / s=8 half-duplex "
                         "/ s=8 zero-bubble arms; writes the "
                         "host-plan/drain/launch split between the "
                         "serving-breakdown sentinels in --out")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--gen", type=int, default=96)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) through "
                    "jax.config")
    args = ap.parse_args()

    from paddle_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    if args.serving:
        meta, arms = run_serving(args.layers, args.hidden, args.batch,
                                 args.requests, args.prompt, args.gen,
                                 args.vocab)
        emit_serving_markdown(meta, arms, args.out)
        return

    meta = run_and_trace(args.model, args.layers, args.hidden, args.batch,
                         args.seq, args.vocab, args.iters, args.trace_dir)
    totals, trace_path = parse_trace(args.trace_dir)
    if not totals:
        print("no trace events captured", file=sys.stderr)
        sys.exit(1)
    emit_markdown(meta, totals, trace_path, args.out)


if __name__ == "__main__":
    main()
