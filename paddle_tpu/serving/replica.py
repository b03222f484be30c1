"""Replica process entry point (ISSUE 12): one OS process, one
ServingEngine, driven over a length-prefixed socket by the parent
router.

    python -m paddle_tpu.serving.replica --store-host H --store-port P \
        --key SESSION/r0e0 [--connect-timeout 120]

Startup contract (the reference's `distributed/launch` per-rank spawn,
collapsed to serving): the child connects to the parent's TCPStore as
a client (the PR 7 rendezvous barrier — the store's connect path
retries until `--connect-timeout`, so slow jax imports on either side
are survivable), binds a loopback listener on an ephemeral port,
publishes it under `<key>/port`, bumps the `<session>/arrived`
arrival counter, and accepts exactly one connection: the parent's
command channel. Everything after that is the command loop below.

Command vocabulary (JSON header + optional binary page frames — see
wire.py): init (build runner via an importable factory, optionally
ServingEngine.restore from a snapshot), submit / abort / step / flush
/ snapshot / inject / extract / handoff_extract / handoff_inject /
release_prefix_cache / check_no_leaks / metrics / audit / ping /
shutdown. Every reply carries a `stats` block (queue depth, running
count, waiting ids, allocator counters, staged handoffs) so the
parent's routing/load decisions never need an extra round trip.

Failure semantics are deliberately blunt: command-level load errors
(queue full, unknown request) travel back as tagged error replies,
but anything else — including an injected ReplicaCrashError — escapes
the loop and kills the process with a traceback. A dead process is
the failure unit here; the parent detects the EOF (or the waitpid
exit code, or a heartbeat timeout for SIGSTOP-style hangs) and the
Supervisor's fence -> respawn -> restore -> backfill machinery takes
over, exactly as it does for a crashed thread.
"""

from __future__ import annotations

import argparse
import importlib
import socket
import sys


def resolve_factory(spec: dict):
    """Import `module:callable` (after prepending spec["sys_path"]) —
    how a child process rebuilds the parent's runner factory without
    pickling code objects."""
    for p in spec.get("sys_path", ()) or ():
        if p not in sys.path:
            sys.path.insert(0, p)
    mod_name, _, fn_name = spec["factory"].partition(":")
    if not fn_name:
        raise ValueError(
            f"factory spec {spec['factory']!r} must be 'module:callable'")
    mod = importlib.import_module(mod_name)
    return getattr(mod, fn_name)


def model_runner_factory(index: int = 0, *, model: str = "llama",
                         seed: int = 0, **kw):
    """Built-in factory for real-model replicas: builds a Llama/GPT
    PagedModelRunner, seeded — every process that calls this with the
    same arguments holds IDENTICAL weights, which is what makes
    cross-process migration token-exact without ever shipping
    parameters over the wire. `kw` holds the runner's options
    (`RUNNER_OPTIONS`) and, under every other name, the model's config."""
    import paddle_tpu as paddle
    from paddle_tpu.serving.model_runner import RUNNER_OPTIONS, build_runner

    runner_kw = {k: kw.pop(k) for k in RUNNER_OPTIONS if k in kw}
    paddle.seed(seed)
    if model == "llama":
        from paddle_tpu.models.llama import Llama, LlamaConfig

        net = Llama(LlamaConfig(**kw))
    elif model == "gpt":
        from paddle_tpu.models.gpt import GPT, GPTConfig

        net = GPT(GPTConfig(**kw))
    else:
        raise ValueError(f"model={model!r}; expected 'llama' or 'gpt'")
    net.eval()
    return build_runner(net, **runner_kw)


class ReplicaServer:
    """The child-side command loop around one ServingEngine."""

    def __init__(self):
        self.engine = None
        self._kv_store = None       # SharedKVStoreClient when attached
        self.steps = 0
        # finished outputs the parent has ACKED (ISSUE 13): outputs are
        # re-shipped in every reply until the parent acks them in a
        # later command header — a reply lost to a deadline trip or a
        # CRC reject can therefore never lose a finished output
        self._acked = set()

    # ------------------------------------------------------------ state

    def _stats(self) -> dict:
        eng = self.engine
        a = eng.pool.allocator
        return {
            "queue_depth": eng.scheduler.queue_depth,
            "running": len(eng.scheduler.running),
            "waiting_ids": [r.request_id for r in eng.scheduler.waiting],
            "num_free": a.num_free,
            "num_evictable": a.num_evictable,
            "num_usable": a.num_usable,
            "has_work": eng.has_work(),
            "handoffs": eng.handoff_ready(),
            "steps": self.steps,
        }

    def _new_outputs(self) -> dict:
        from paddle_tpu.serving.wire import outputs_to_wire

        fresh = {rid: o for rid, o in self.engine._outputs.items()
                 if rid not in self._acked}
        return outputs_to_wire(fresh)

    def _reply(self, **extra) -> dict:
        out = {"ok": True, "stats": self._stats(),
               "outputs": self._new_outputs()}
        out.update(extra)
        return out

    def _requests_view(self) -> dict:
        return {rid: {"done": r.done, "arrival_index": r.arrival_index}
                for rid, r in self.engine._requests.items()}

    # --------------------------------------------------------- commands

    def handle(self, header: dict, bufs):
        from paddle_tpu.serving.engine import ServingEngine
        from paddle_tpu.serving.resilience import (
            InvariantViolation, QueueFullError, audit_engine,
        )
        from paddle_tpu.serving.wire import (
            events_to_wire, handoff_from_wire, handoff_to_wire,
            sampling_from_dict, state_from_wire, state_to_wire,
        )

        cmd = header["cmd"]
        self._acked.update(header.get("ack_outputs", ()))
        if cmd == "init":
            factory = resolve_factory(header["spec"])
            try:
                runner = factory(int(header.get("index", 0)),
                                 **header["spec"].get("factory_kw", {}))
            except TypeError:       # index-blind factories are fine too
                runner = factory(**header["spec"].get("factory_kw", {}))
            # cluster-wide KV attach (ISSUE 14): map the router's
            # shared-memory segments and open the metadata channel —
            # this engine's host tier then IS the host-wide store,
            # under this child's unique owner tag
            store_info = header.get("store")
            kv_store = kv_owner = None
            if store_info is not None:
                from paddle_tpu.serving.store_service import (
                    SharedKVStoreClient,
                )

                kv_store = SharedKVStoreClient(store_info["attach"],
                                               store_info["addr"])
                kv_owner = store_info.get("owner")
                self._kv_store = kv_store
            snap = header.get("snapshot")
            if snap is not None:
                self.engine = ServingEngine.restore(
                    runner, snap, kv_store=kv_store,
                    kv_store_owner=kv_owner)
            else:
                self.engine = ServingEngine(runner,
                                            kv_store=kv_store,
                                            kv_store_owner=kv_owner,
                                            **header["engine_kw"])
            return self._reply(
                block_size=self.engine.pool.block_size,
                max_batch_size=self.engine.max_batch_size,
                role=self.engine.role,
                requests=self._requests_view())
        if cmd == "ping":
            return self._reply()
        if cmd == "submit":
            sampling = sampling_from_dict(header["sampling"])
            try:
                rid = self.engine.add_request(
                    header["prompt_tokens"], sampling,
                    request_id=header.get("request_id"))
            except QueueFullError as e:
                return {"ok": False, "error": "queue_full",
                        "message": str(e), "stats": self._stats(),
                        "outputs": self._new_outputs()}
            arrival = self.engine._requests[rid].arrival_index
            return self._reply(request_id=rid, arrival_index=arrival)
        if cmd == "abort":
            ok = self.engine.abort(header["request_id"],
                                   header.get("reason", "aborted"))
            return self._reply(aborted=ok)
        if cmd == "step":
            events = self.engine.step() if self.engine.has_work() else []
            if events or self.engine.has_work():
                self.steps += 1
            return self._reply(events=events_to_wire(events))
        if cmd == "flush":
            return self._reply(events=events_to_wire(self.engine.flush()))
        if cmd == "snapshot":
            return self._reply(snapshot=self.engine.snapshot())
        if cmd == "inject":
            state = state_from_wire(header["state"])
            rid = self.engine.inject_request(
                state["prompt_tokens"], state["sampling"],
                request_id=state["request_id"],
                output_tokens=state.get("output_tokens", ()),
                arrival_index=state.get("arrival_index"),
                num_preemptions=int(state.get("num_preemptions", 0)),
                elapsed_s=float(state.get("elapsed_s", 0.0)),
                first_token_elapsed_s=state.get("first_token_elapsed_s"))
            return self._reply(request_id=rid)
        if cmd == "extract":
            try:
                state = self.engine.extract_request(header["request_id"])
            except (KeyError, ValueError) as e:
                return {"ok": False, "error": type(e).__name__,
                        "message": str(e), "stats": self._stats(),
                        "outputs": self._new_outputs()}
            return self._reply(state=state_to_wire(state))
        if cmd == "handoff_extract":
            try:
                state, payload = self.engine.extract_handoff(
                    header["request_id"])
            except KeyError as e:
                return {"ok": False, "error": "KeyError",
                        "message": str(e), "stats": self._stats(),
                        "outputs": self._new_outputs()}
            head, frames = handoff_to_wire(payload)
            head.update(self._reply(state=state_to_wire(state)))
            return head, frames
        if cmd == "handoff_inject":
            payload = handoff_from_wire(header, bufs)
            state = state_from_wire(header["state"])
            try:
                rid = self.engine.import_handoff(state, payload)
            except ValueError as e:     # content-hash mismatch: loud
                return {"ok": False, "error": "handoff_corrupt",
                        "message": str(e), "stats": self._stats(),
                        "outputs": self._new_outputs()}
            return self._reply(request_id=rid)
        if cmd == "stage_migration":
            # graceful drain (ISSUE 13): park one RUNNING request in
            # the handoff buffer so its KV pages can ride to a sibling
            return self._reply(
                staged=self.engine.stage_migration(header["request_id"]))
        if cmd == "release_prefix_cache":
            return self._reply(released=self.engine.release_prefix_cache())
        if cmd == "check_no_leaks":
            return self._reply(
                no_leaks=self.engine.pool.allocator.check_no_leaks())
        if cmd == "metrics":
            return self._reply(snapshot=self.engine.metrics.snapshot())
        if cmd == "audit":
            try:
                audit_engine(self.engine)
            except InvariantViolation as e:
                return self._reply(problems=str(e))
            return self._reply(problems=None)
        if cmd == "requests":
            return self._reply(requests=self._requests_view())
        if cmd == "shutdown":
            return self._reply(bye=True)
        raise ValueError(f"unknown command {cmd!r}")

    def serve(self, conn: socket.socket) -> None:
        from paddle_tpu.serving.wire import (
            WireCorruptionError, recv_msg, send_msg,
        )

        while True:
            try:
                header, bufs = recv_msg(conn)
            except WireCorruptionError as e:
                # the parent's request frame failed its CRC (ISSUE 13):
                # the advertised bytes were consumed so the stream is
                # still framed — NAK it (seq=None marks "your current
                # request", the client retries idempotent RPCs) and
                # keep serving. Never parse corrupted bytes as a
                # command.
                send_msg(conn, {"ok": False, "error": "wire_corrupt",
                                "seq": None, "message": str(e)})
                continue
            out = self.handle(header, bufs)
            if isinstance(out, tuple):
                reply, frames = out
            else:
                reply, frames = out, ()
            # echo the sequence number: the client matches replies to
            # attempts with it, so a reply that arrives after its
            # attempt's deadline is recognized as stale, folded for its
            # stats/outputs, and never mistaken for the retry's answer
            reply.setdefault("seq", header.get("seq"))
            send_msg(conn, reply, frames)
            if header["cmd"] == "shutdown":
                return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("paddle_tpu.serving.replica")
    ap.add_argument("--store-host", required=True)
    ap.add_argument("--store-port", type=int, required=True)
    ap.add_argument("--key", required=True,
                    help="rendezvous key prefix, e.g. SESSION/r0e0")
    ap.add_argument("--session", default=None,
                    help="session prefix for the arrival counter")
    ap.add_argument("--connect-timeout", type=float, default=120.0)
    ap.add_argument("--accept-timeout", type=float, default=300.0)
    args = ap.parse_args(argv)

    from paddle_tpu.parallel.store import TCPStore

    store = TCPStore(args.store_host, args.store_port, is_master=False,
                     timeout=args.connect_timeout,
                     connect_timeout=args.connect_timeout)
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    port = lst.getsockname()[1]
    # the rendezvous: publish the command port, bump the arrival
    # counter — the parent waits on these with a deadline and names
    # any rank that never showed up
    store.set(f"{args.key}/port", str(port))
    if args.session:
        store.add(f"{args.session}/arrived", 1)
    lst.settimeout(args.accept_timeout)
    try:
        conn, _ = lst.accept()
    except socket.timeout:
        print(f"replica {args.key}: parent never connected within "
              f"{args.accept_timeout:.0f}s", file=sys.stderr)
        return 3
    conn.settimeout(None)
    lst.close()
    ReplicaServer().serve(conn)
    return 0


if __name__ == "__main__":
    sys.exit(main())
