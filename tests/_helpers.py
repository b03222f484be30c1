"""Shared test utilities (plain module — conftest.py must stay import-free
of test code so pytest's rootdir-relative conftest loading can't execute
it twice under two module names)."""

import os


def assert_dequantized_equal(out, ref):
    """`out` (a compiled quantized collective) against `ref` (its numpy
    oracle): code * scale, element by element. The codes are integers
    and agree exactly. The scale does not: XLA compiles `absmax / 127`
    as a multiplication by the reciprocal, up to one unit in the last
    place off numpy's division, and the product then rounds once more,
    so the floats agree to about two units (measured: max rel 2e-7,
    1.7-13 % of the elements). One code off would be a relative error
    of at least 1 / (127 * 127 * shards), fifty times this bound, so
    the comparison still pins every code."""
    import numpy as np

    np.testing.assert_allclose(out, ref, rtol=2.5e-7, atol=0)


class StubPagedRunner:
    """A numpy paged-KV 'model' with the PagedModelRunner step interface.

    The KV pool is the single source of history: prefill/decode write the
    raw token ids through the block table (layer 0, head 0, dim 0) and the
    next-token logits are a deterministic hash of the FULL gathered
    history — so any scheduler/allocator/block-table bug (wrong page,
    stale table, cross-sequence aliasing) changes the generated tokens and
    breaks oracle equivalence. No jit, no model math: fast enough for
    hundreds of fuzz trials.
    """

    num_layers = 1
    n_heads = 1
    n_kv_heads = 1
    head_dim = 1

    def __init__(self, vocab_size=31, block_size=4, max_model_len=64):
        import jax.numpy as jnp

        self.vocab_size = vocab_size
        self.block_size = block_size
        self.max_model_len = max_model_len
        self.dtype = jnp.float32
        # per-row decode_multi steps actually computed (ISSUE 11: the
        # early-stop saves-compute pin counts frozen rows' skipped work)
        self.counted_row_steps = 0

    def recipe(self):
        """PagedModelRunner.recipe(): the runner's half of a snapshot."""
        return {"block_size": self.block_size,
                "max_model_len": self.max_model_len,
                "kv_dtype": getattr(self, "kv_dtype", "fp32"),
                "weight_dtype": "fp32", "weight_group_size": 128,
                "comm_dtype": "fp32"}

    def _logits(self, history):
        import numpy as np

        h = 7
        for i, t in enumerate(history):
            h = (h * 131 + (i + 1) * (int(t) + 1)) % self.vocab_size
        row = np.zeros((self.vocab_size,), np.float32)
        row[h] = 1.0
        return row

    def prefill(self, tokens, table, pools):
        return self.prefill_chunk(tokens, 0, table, pools)

    def prefill_chunk(self, tokens, start_pos, table, pools):
        """Write the chunk's tokens at positions [start_pos, ...) and hash
        the FULL history as gathered from the pool — so a wrong shared
        -prefix page, a stale chunk boundary, or a COW miss changes the
        logits and breaks oracle equivalence."""
        import jax.numpy as jnp
        import numpy as np

        (k, v), = pools
        k = np.array(k)
        for i, t in enumerate(tokens):
            p = start_pos + i
            page = int(table[p // self.block_size])
            k[page, p % self.block_size, 0, 0] = float(t)
        end = start_pos + len(tokens)
        hist = [k[int(table[i // self.block_size]),
                  i % self.block_size, 0, 0] for i in range(end)]
        return (jnp.asarray(self._logits(hist)),
                [(jnp.asarray(k), v)])

    def decode(self, tokens, tables, pos, pools):
        import jax.numpy as jnp
        import numpy as np

        (k, v), = pools
        k = np.array(k)
        tokens = np.asarray(tokens)
        tables = np.asarray(tables)
        pos = np.asarray(pos)
        B = tokens.shape[0]
        out = np.zeros((B, self.vocab_size), np.float32)
        for b in range(B):
            p = int(pos[b])
            page = int(tables[b, p // self.block_size])
            k[page, p % self.block_size, 0, 0] = float(tokens[b])
            hist = [k[int(tables[b, i // self.block_size]),
                      i % self.block_size, 0, 0] for i in range(p + 1)]
            out[b] = self._logits(hist)
        return jnp.asarray(out), [(jnp.asarray(k), v)]

    def decode_multi(self, tokens, tables, pos, pools, num_steps,
                     seeds=None, base_steps=None, temps=None,
                     top_k=None, top_p=None, stop_ids=None,
                     remaining=None, early_stop=False):
        """Device-resident horizon (ISSUE 6): num_steps consecutive
        decode steps, each step's token fed back as the next input,
        history gathered from the pool every step — so a missing
        pre-committed horizon page, a stale table, or a wrong feedback
        token changes the buffer and breaks oracle equality. Returns
        the packed [2, B, s] (tokens, finite-flags) buffer the real
        runner's scan emits — or, with the ISSUE-11 extension operands
        (per-row seeded sampling via the engine's own `seeded_sample`
        host math, and/or the on-device stop flag that freezes a done
        row's KV writes), the extended [3, B, s] buffer with the LIVE
        plane. `counted_row_steps` tallies the per-row steps actually
        computed, so tests can pin that early stop SAVES compute."""
        import jax.numpy as jnp
        import numpy as np

        (k, v), = pools
        k = np.array(k)
        tokens = np.asarray(tokens).copy()
        tables = np.asarray(tables)
        pos = np.asarray(pos).copy()
        B = tokens.shape[0]
        extended = temps is not None or early_stop
        toks = np.zeros((B, num_steps), np.int32)
        fins = np.zeros((B, num_steps), np.int32)
        lives = np.zeros((B, num_steps), np.int32)
        done = np.zeros((B,), bool)
        cnt = np.zeros((B,), np.int32)
        for t in range(num_steps):
            for b in range(B):
                if done[b]:
                    continue          # frozen row: no write, no compute
                p = int(pos[b])
                page = int(tables[b, p // self.block_size])
                k[page, p % self.block_size, 0, 0] = float(tokens[b])
                hist = [k[int(tables[b, i // self.block_size]),
                          i % self.block_size, 0, 0] for i in range(p + 1)]
                row = self._logits(hist)
                self.counted_row_steps += 1
                if (temps is not None and float(temps[b]) > 0.0
                        and np.all(np.isfinite(row))):
                    from paddle_tpu.serving.engine import seeded_sample

                    toks[b, t] = seeded_sample(
                        row, int(seeds[b]), int(base_steps[b]) + int(cnt[b]),
                        float(temps[b]), top_k, top_p)
                else:
                    toks[b, t] = int(np.argmax(row))
                fins[b, t] = int(np.all(np.isfinite(row)))
                lives[b, t] = 1
                cnt[b] += 1
                if early_stop:
                    hit = (stop_ids is not None
                           and toks[b, t] in set(int(x)
                                                 for x in stop_ids[b]))
                    if hit or cnt[b] >= int(remaining[b]):
                        done[b] = True
                tokens[b] = toks[b, t]
                pos[b] += 1
        planes = [toks, fins] + ([lives] if extended else [])
        return (jnp.asarray(np.stack(planes)),
                [(jnp.asarray(k), v)])

    def decode_multi_spec(self, tokens, tables, pos, pools, drafts,
                          seeds=None, base_steps=None, temps=None,
                          top_k=None, top_p=None, stop_ids=None,
                          remaining=None):
        """Fused verify-in-scan horizon (ISSUE 18): each scan step
        carries a per-row draft span (drafts[b, t], -1-padded) — the
        span's tokens are written through the block table position by
        position, each position's emission is resolved with the SAME
        seeded/greedy math as decode_multi, and the kept prefix is the
        run of matching-draft positions that hit no stop/budget wall
        (position 0, the fed token's emission, is always kept while the
        row is live). The last kept emission feeds the next scan step.
        Returns the packed [3, B, s, K+1] buffer (tokens, finiteness,
        keep planes) the real runner's scan emits. Rejected-tail writes
        land in the pool exactly like the device's (overwritten by the
        next span before any query can attend them), so a missed host
        rollback still breaks oracle equivalence."""
        import jax.numpy as jnp
        import numpy as np

        (k, v), = pools
        k = np.array(k)
        tokens = np.asarray(tokens).copy()
        tables = np.asarray(tables)
        pos = np.asarray(pos).copy()
        drafts = np.asarray(drafts)
        B, num_steps, K = drafts.shape
        T = K + 1
        toks = np.zeros((B, num_steps, T), np.int32)
        fins = np.zeros((B, num_steps, T), np.int32)
        keeps = np.zeros((B, num_steps, T), np.int32)
        done = np.zeros((B,), bool)
        cnt = np.zeros((B,), np.int32)
        for t in range(num_steps):
            for b in range(B):
                if done[b]:
                    continue          # frozen row: no write, no compute
                row_draft = drafts[b, t]
                ndraft = int(np.sum(row_draft >= 0))
                span = [int(tokens[b])] + [int(x)
                                           for x in row_draft[:ndraft]]
                self.counted_row_steps += 1
                stops = (set(int(x) for x in stop_ids[b] if int(x) >= 0)
                         if stop_ids is not None else set())
                rem = (int(remaining[b]) if remaining is not None
                       else 1 << 30)
                kept = 0
                for i, tok_in in enumerate(span):
                    p = int(pos[b]) + i
                    if p >= self.max_model_len:
                        break         # the device's wall mask
                    page = int(tables[b, p // self.block_size])
                    k[page, p % self.block_size, 0, 0] = float(tok_in)
                    hist = [k[int(tables[b, j // self.block_size]),
                              j % self.block_size, 0, 0]
                            for j in range(p + 1)]
                    row = self._logits(hist)
                    if (temps is not None and float(temps[b]) > 0.0
                            and np.all(np.isfinite(row))):
                        from paddle_tpu.serving.engine import seeded_sample

                        nxt = seeded_sample(
                            row, int(seeds[b]),
                            int(base_steps[b]) + int(cnt[b]) + i,
                            float(temps[b]), top_k, top_p)
                    else:
                        nxt = int(np.argmax(row))
                    toks[b, t, i] = nxt
                    fins[b, t, i] = int(np.all(np.isfinite(row)))
                    if i == kept:     # still on the kept prefix
                        keeps[b, t, i] = 1
                        kept += 1
                        pos_done = (nxt in stops
                                    or int(cnt[b]) + 1 + i >= rem)
                        if pos_done:
                            done[b] = True
                        matched = (i < ndraft
                                   and int(row_draft[i]) == nxt)
                        if pos_done or not matched:
                            # later span positions still write KV (the
                            # device can't know acceptance pre-forward)
                            # but nothing past here is kept
                            pass
                        else:
                            continue
                        # freeze the kept prefix; keep writing the tail
                        kept = -1
                tokens[b] = int(toks[b, t, max(
                    0, int(np.sum(keeps[b, t])) - 1)])
                cnt[b] += int(np.sum(keeps[b, t]))
                pos[b] += int(np.sum(keeps[b, t]))
        return (jnp.asarray(np.stack([toks, fins, keeps])),
                [(jnp.asarray(k), v)])

    def ragged_step(self, tokens, tables, start_pos, q_lens, pools,
                    full_logits=False):
        """Mixed ragged batch (fused chunk+decode and the ISSUE-5 verify
        step): each slot writes its span's tokens through its own block
        table and row i scores the pool-gathered history THROUGH span
        position i — so a stale table, a wrong speculative write, or a
        missed rollback changes the logits and breaks oracle equality."""
        import jax.numpy as jnp
        import numpy as np

        (k, v), = pools
        k = np.array(k)
        tokens = np.asarray(tokens)
        tables = np.asarray(tables)
        start_pos = np.asarray(start_pos)
        q_lens = np.asarray(q_lens)
        B, T = tokens.shape
        full = np.zeros((B, T, self.vocab_size), np.float32)
        for b in range(B):
            for i in range(int(q_lens[b])):
                p = int(start_pos[b]) + i
                page = int(tables[b, p // self.block_size])
                k[page, p % self.block_size, 0, 0] = float(tokens[b, i])
                hist = [k[int(tables[b, j // self.block_size]),
                          j % self.block_size, 0, 0] for j in range(p + 1)]
                full[b, i] = self._logits(hist)
        if full_logits:
            return jnp.asarray(full), [(jnp.asarray(k), v)]
        last = np.stack([full[b, max(int(q_lens[b]) - 1, 0)]
                         for b in range(B)])
        return jnp.asarray(last), [(jnp.asarray(k), v)]


class PeriodicStubRunner(StubPagedRunner):
    """Stub whose greedy continuation is PERIODIC: the next token repeats
    the token `period` positions back in the pool-gathered history (so
    block-table/rollback bugs still break it). Decoding a periodic
    prompt yields a periodic output — the n-gram prompt-lookup proposer
    hits almost every step, which makes this the repetition-heavy
    workload for the ISSUE-5 steps-per-token acceptance pin."""

    def __init__(self, period=4, **kw):
        super().__init__(**kw)
        self.period = period

    def _logits(self, history):
        import numpy as np

        if len(history) >= self.period:
            nxt = int(history[-self.period]) % self.vocab_size
        else:
            nxt = (7 * (len(history) + 1)) % self.vocab_size
        row = np.zeros((self.vocab_size,), np.float32)
        row[nxt] = 1.0
        return row


class CountingStubRunner(StubPagedRunner):
    """The stub as a runner that counts: each single-pass launch hands its
    counts over on the device (`COUNTS`, `on_step_counts`), bumps the two
    host-side counters it says it keeps (`GAUGES`), and fails once when
    told to, before anything reaches the pools. A subclass renames both."""

    COUNTS = ("moe_tokens_routed", "moe_local_pairs")
    GAUGES = ("attn_kv_bytes_read", "attn_kv_bytes_gather")

    def __init__(self, fail_decode_calls=(), **kw):
        super().__init__(**kw)
        self.on_step_counts = None
        for name in self.GAUGES:
            setattr(self, name, 0.0)
        self.handed = []                  # every launch's counts, in order
        self.decode_calls = 0
        self.fail_decode_calls = set(fail_decode_calls)

    def _count(self, tokens: int, rows: int) -> None:
        import jax.numpy as jnp

        for name, n in zip(self.GAUGES, (16.0 * tokens, 64.0 * rows)):
            setattr(self, name, getattr(self, name) + n)
        self.handed.append((tokens, 3 * rows))
        if self.on_step_counts is not None:
            self.on_step_counts(jnp.asarray([tokens, 3 * rows], jnp.int32))

    def prefill_chunk(self, tokens, start_pos, table, pools):
        out = super().prefill_chunk(tokens, start_pos, table, pools)
        self._count(len(tokens), 1)
        return out

    def decode(self, tokens, tables, pos, pools):
        from paddle_tpu.serving.resilience import InjectedDeviceError

        self.decode_calls += 1
        if self.decode_calls in self.fail_decode_calls:
            raise InjectedDeviceError(f"decode call {self.decode_calls}")
        out = super().decode(tokens, tables, pos, pools)
        self._count(len(tokens), len(tokens))
        return out


def stub_runner_factory(index=0, vocab_size=31, block_size=4,
                        max_model_len=64, period=0):
    """Importable replica-process factory (ISSUE 12): the launcher spec
    `{"factory": "_helpers:stub_runner_factory", "sys_path": [tests/]}`
    rebuilds a StubPagedRunner inside each replica child — the runners
    are deterministic, so every process computes identical streams."""
    if period:
        return PeriodicStubRunner(period=period, vocab_size=vocab_size,
                                  block_size=block_size,
                                  max_model_len=max_model_len)
    return StubPagedRunner(vocab_size=vocab_size, block_size=block_size,
                           max_model_len=max_model_len)


def paged_decode_attention(q, kp, vp, tbl, pos, interpret=True):
    """[b, h, d] single-token decode queries as one-row spans of the
    ragged paged-attention kernel (its q_len == 1 case)."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention

    return ragged_paged_attention(q[:, None], kp, vp, tbl, pos, 1,
                                  interpret=interpret)[:, 0]


def child_env(repo_on_pythonpath=True, num_cpu_devices=None):
    """Env for spawning CPU-only child processes from tests.

    Children target the CPU backend: a chip belongs to one process at a
    time, so where the parent holds one a child that reached for it would
    fail or hang. Every test that spawns a subprocess should build its env
    here.

    num_cpu_devices: pin the child's virtual CPU device count — the
    parent's conftest default (JAX_NUM_CPU_DEVICES=8) would otherwise
    leak into the child, and multi-process tests would see 8 devices per
    rank instead of 1, breaking every world-mesh shape.
    """
    env = dict(os.environ)
    if repo_on_pythonpath:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # device-manager tests register fake PJRT plugins; a leaked registry
    # would make the child's jax plugin discovery dlopen dead stub paths
    env.pop("PJRT_NAMES_AND_LIBRARY_PATHS", None)
    env.pop("CUSTOM_DEVICE_ROOT", None)
    if num_cpu_devices is not None:
        env["JAX_NUM_CPU_DEVICES"] = str(num_cpu_devices)
    return env


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (custom_vjp, pjit, ...), a kernel's own body apart."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)


def kernel_names(jaxpr) -> list:
    """The `pallas_call`s a jaxpr reaches, in order, by their own names."""
    return [e.params["name"] for e in equations(jaxpr)
            if e.primitive.name == "pallas_call"]

