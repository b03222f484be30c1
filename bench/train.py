"""Training cells: drive paddle_tpu's `jit.TrainStep` on the configuration's model.

Set-up builds ONE TrainStep (fp32 parameters and AdamW state, bf16 autocast)
on weights drawn by the configuration's reference, drives it through its first
`check_steps` steps with the window's own feed (a fresh batch per step drawn
on the device from the seed), and hands the same object to the window. The
reference follows those steps from the same weights and batches, before the
program's state exists; its time is not counted in setup_s.
"""

from __future__ import annotations

import contextlib
import functools
import math
import statistics
import time

import numpy as np

import traffic_gen
from run import load_json, say

# control: the reference with bfloat16 master weights and optimizer state in
# the program's place (no program, no window)
PROBES = ("ref-bfloat16",)


def _reference_placement(run, cfg, reference):
    """Where the reference keeps its weights. One chip: there. Several:
    each leaf split over all of them on its last axis that divides (plain
    GSPMD, so that 1.3 B float32 parameters with their gradient and Adam
    state, 16 bytes each, fit), rows of a block split the same way."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = len(run.devices)
    mesh = Mesh(np.array(run.devices), ("r",))
    shapes = jax.eval_shape(functools.partial(reference.init_weights, cfg),
                            reference.seed_key(0))

    def spec(shape):
        for ax in reversed(range(len(shape))):
            if n > 1 and len(shape) > 1 and shape[ax] % n == 0:
                return P(*([None] * ax + ["r"]))
        return P()

    weights = {k: NamedSharding(mesh, spec(v.shape))
               for k, v in shapes.items()}
    return weights, NamedSharding(mesh, P("r") if n > 1 else P())


def _gap(prog: dict, ref: dict) -> tuple:
    """Worst leaf: |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref.values())
    worst = max(ref, key=lambda k: abs(prog[k] - ref[k]) / max(ref[k], med))
    return abs(prog[worst] - ref[worst]) / max(ref[worst], med), worst


def drive(run) -> dict:
    try:
        return _drive(run)
    finally:
        if len(run.devices) > 1:        # init_mesh set a process-wide mesh
            from paddle_tpu.parallel.mesh import set_mesh

            set_mesh(None)


def _drive(run) -> dict:
    if run.probe and run.probe not in PROBES:
        raise SystemExit(f"unknown probe {run.probe!r} for a training cell")
    import jax
    import jax.numpy as jnp
    import jax.profiler

    cfg, tr, reference = run.model_cfg(), run.traffic, run.reference
    batch, seq, vocab = tr["batch"], tr["seq_len"], cfg["vocab_size"]
    n_check = tr["check_steps"]
    opt_args = {k: v for k, v in tr["optimizer"].items() if k != "name"}
    limits = load_json(run.files, "limits", run.cell["name"] + ".json")
    w_shard, row_shard = _reference_placement(run, cfg, reference)

    weights = jax.jit(functools.partial(reference.init_weights, cfg),
                      out_shardings=w_shard)(reference.seed_key(run.seed))
    data_key = reference.seed_key(run.seed, 1)
    feed = jax.jit(functools.partial(traffic_gen.train_batch, batch=batch,
                                     seq=seq, vocab=vocab))

    # ---- the reference, before the program's state exists
    t0 = time.perf_counter()
    batches = tuple(
        tuple(jax.device_put(x, row_shard) for x in feed(data_key, i + 1))
        for i in range(n_check))

    def readings(precision):
        fn = jax.jit(functools.partial(
            reference.train_readings, cfg, opt=opt_args,
            rows_per_block=tr["reference_rows_per_block"],
            precision=precision))
        losses, gnorm, dnorm = fn(weights, batches)
        to_f = lambda d: {k: float(v) for k, v in d.items()}
        return [float(x) for x in losses], to_f(gnorm), to_f(dnorm)

    ref_loss, ref_g, ref_d = readings("float32")
    say(f"reference: losses {ref_loss} in {time.perf_counter() - t0:.1f} s")
    if run.probe == "ref-bfloat16":
        losses, g, d = readings("bfloat16")
        del weights
    del batches
    run.excluded_s += time.perf_counter() - t0

    # ---- the program
    if not run.probe:
        import paddle_tpu as paddle
        from paddle_tpu import parallel as dist

        sharded = len(run.devices) > 1
        mesh_args = {}
        if sharded:
            dist.init_mesh(run.config["four_chip_layout"],
                           devices=run.devices)
            mesh_args = run.config["program"]["mesh_args"]
        # TrainStep builds its copies and the optimizer's zeros on the default
        # device before it shards them: for a model that needs the mesh that
        # device must be the host, or chip 0 alone would have to hold it all
        place = (jax.default_device(jax.devices("cpu")[0]) if sharded
                 else contextlib.nullcontext())
        with place:
            model = run.program("model")(
                run.program("config")(**mesh_args, **cfg))
            named = reference.program_names(weights)
            del weights
            # Tensors, not raw arrays: set_state_dict takes anything else
            # through numpy on the host
            missing, unexpected = model.set_state_dict(
                {k: paddle.Tensor(v) for k, v in named.items()})
            del named
            if missing or unexpected:
                raise SystemExit(f"weights do not fit the model: missing "
                                 f"{missing}, unexpected {unexpected}")
            opt = getattr(paddle.optimizer, tr["optimizer"]["name"])(
                parameters=model.parameters(), **opt_args)
            step = paddle.jit.TrainStep(model, run.program("loss"), opt,
                                        amp_level=tr["amp_level"])

        def one_step(i):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                tokens, labels = feed(data_key, i)
                return step(tokens, labels)._value

        norms = jax.jit(lambda tree: {
            k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()})
        losses = []
        for i in range(1, n_check + 1):
            losses.append(float(one_step(i)))
            if i == 1:      # the first gradient, as the optimizer got it
                g = {k: float(v) / (1 - opt_args["beta1"]) for k, v in norms(
                    {k: s["moment1"] for k, s in step.opt_state.items()}
                ).items()}
        # the starting weights are drawn again inside the jit (never all
        # alive at once), so the bench holds no second copy through the steps
        d = {k: float(v) for k, v in jax.jit(lambda p, key: {
            k: jnp.sqrt(jnp.sum(jnp.square(p[k] - w0)))
            for k, w0 in reference.program_names(
                reference.init_weights(cfg, key)).items()})(
                    step.params, reference.seed_key(run.seed)).items()}
        del model

    say(f"losses {losses}")
    ok = run.check("loss_gap_max", max(abs(a - b) for a, b in
                                       zip(losses, ref_loss)),
                   limits["loss_gap_max"])
    gap, leaf = _gap(g, ref_g)
    say(f"first gradient: worst leaf {leaf}")
    ok &= run.check("grad_norm_gap_worst_leaf", gap,
                    limits["grad_norm_gap_worst_leaf"])
    gap, leaf = _gap(d, ref_d)
    say(f"parameters' change after {n_check} steps: worst leaf {leaf}")
    ok &= run.check("delta_norm_gap_worst_leaf", gap,
                    limits["delta_norm_gap_worst_leaf"])
    if run.probe:
        run.setup_s = run.setup_seconds()
        return run.result(ok, 0, 0, {}, {"memory_peak_bytes":
                                         run.memory_peak_bytes()})

    # ---- the window: the same TrainStep, the same feed
    run.open_window()
    spans, window_losses, prev = [], [], None
    i = n_check
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < run.seconds:
        i += 1
        t0 = time.perf_counter()
        loss = one_step(i)
        if prev is not None:        # at most two steps in flight
            prev.block_until_ready()
        spans.append((t0, time.perf_counter()))
        window_losses.append(loss)
        prev = loss
        run.tick()
    prev.block_until_ready()
    t_close = time.perf_counter()
    run.close_window()
    n = len(spans)
    window_losses = [float(x) for x in window_losses]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    say(f"window: {n} steps of {batch} x {seq} tokens in "
        f"{t_close - t_open:.3f} s; last loss {window_losses[-1]:.4f}; "
        f"compiles in window: {run.compiles}")
    e2e = {"train_tokens_per_s": n * batch * seq / (t_close - t_open)}
    correct = ok and failed == 0 and run.compiles == 0
    ctx = {"memory_peak_bytes": run.memory_peak_bytes(), "steps": spans,
           "batch": batch, "seq": seq, "median": statistics.median,
           "t_open": t_open}
    return run.result(correct, n, failed, e2e, ctx)
