"""Process-spawned pipeline stages: cross-process stage disaggregation.

The TPU counterpart of the reference's stage worker spawn (reference:
entrypoints/omni_stage.py:394-504 — mp.Process per stage with a
``stage_ready`` handshake :733; per-stage device env via
``set_stage_devices``, stage_utils.py).  Each ``ProcStage`` owns a child
process running a full in-proc ``OmniStage`` (engine included); the
orchestrator talks to it over a framed TCP socket, so the same worker can
run on another host (stage disaggregation across TPU-VM slices — pass a
routable bind host).

Device isolation: a single TPU chip admits one process, so per-stage
``device_env`` (e.g. {"JAX_PLATFORMS": "cpu"} or TPU_VISIBLE_CHIPS
selections) is applied in the child *before* jax import — the analogue of
CUDA_VISIBLE_DEVICES stage scoping.

Frames are length-prefixed OmniSerializer payloads (tensor-aware), the
same wire format as the TCP connector.
"""

from __future__ import annotations

import multiprocessing as mp
import queue
import socket
import threading
import time
from typing import Any, Optional

from vllm_omni_tpu.analysis.runtime import traced
from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.distributed.serialization import OmniSerializer
from vllm_omni_tpu.distributed.tcp import _recv_frame, _send_frame
from vllm_omni_tpu.entrypoints.omni_stage import OmniStage, StageRequest
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.outputs import OmniRequestOutput
from vllm_omni_tpu.resilience.faults import fault_point

logger = init_logger(__name__)


def _send_msg(sock: socket.socket, msg: dict) -> None:
    _send_frame(sock, OmniSerializer.dumps(msg))


def _recv_msg(sock: socket.socket) -> Optional[dict]:
    frame = _recv_frame(sock)
    return None if frame is None else OmniSerializer.loads(frame)


class _SockChannel:
    """Framed-message channel over a connected TCP socket."""

    def __init__(self, sock: socket.socket):
        self._sock = sock

    def send(self, msg: dict) -> None:
        fault_point("chan")
        _send_msg(self._sock, msg)

    def recv(self) -> Optional[dict]:
        """Blocks; None means the peer hung up."""
        fault_point("chan")
        return _recv_msg(self._sock)

    def settimeout(self, t) -> None:
        self._sock.settimeout(t)

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


class _ShmChannel:
    """Framed-message channel over a pair of native shared-memory rings
    (vllm_omni_tpu.native.ShmRing — the C++ SPSC ring buffer, the
    reference's C-backed shm MessageQueue analogue).  Same-host only;
    lower latency than the socket for large tensor payloads (no kernel
    copy per byte stream)."""

    def __init__(self, tx, rx):
        self._tx = tx
        self._rx = rx
        self._timeout = None

    def send(self, msg: dict) -> None:
        fault_point("chan")
        self._tx.push(OmniSerializer.dumps(msg), timeout=60.0)

    def recv(self) -> Optional[dict]:
        fault_point("chan")
        # socket semantics: block until a message or the channel closes;
        # bounded waits keep the thread interruptible
        while True:
            if self._rx is None:
                return None
            t = self._timeout if self._timeout is not None else 1.0
            frame = self._rx.pop(timeout=t)
            if frame is not None:
                return OmniSerializer.loads(frame)
            if self._timeout is not None:
                raise socket.timeout("shm channel recv timed out")

    def settimeout(self, t) -> None:
        self._timeout = t

    def close(self) -> None:
        tx, rx, self._tx, self._rx = self._tx, self._rx, None, None
        for ring in (tx, rx):
            if ring is not None:
                ring.close()


def _primary_ip() -> str:
    """This host's primary outbound IP (no packets are sent — a UDP
    connect only selects the route)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("10.255.255.255", 1))
        return s.getsockname()[0]
    except OSError:
        return socket.gethostbyname(socket.gethostname())
    finally:
        s.close()


def _start_scoped(proc, device_env: Optional[dict]) -> None:
    """Start a worker process with ``device_env`` applied to the env the
    child is SPAWNED with (temporarily mutating the parent's environ
    around start()).

    Applying device_env only inside the child's main is too late: the
    spawned interpreter imports this package (and with it jax, which
    reads ``JAX_PLATFORMS`` at import) while unpickling its entry point,
    before our code runs."""
    import os

    updates = dict(device_env or {})
    saved = {k: os.environ.get(k) for k in updates}
    os.environ.update({k: str(v) for k, v in updates.items()})
    try:
        proc.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _worker_channel(conn_info) -> "_SockChannel | _ShmChannel":
    """Child side of the orchestrator<->worker channel."""
    kind = conn_info[0]
    if kind == "tcp":
        sock = socket.create_connection(conn_info[1], timeout=60.0)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return _SockChannel(sock)
    if kind == "shm":
        from vllm_omni_tpu.native import ShmRing

        _, c2p, p2c, capacity = conn_info
        # child owns nothing; rings were created by the orchestrator
        return _ShmChannel(tx=ShmRing(c2p, owner=False),
                           rx=ShmRing(p2c, owner=False))
    raise ValueError(f"unknown transport {kind!r}")


# --------------------------------------------------------------- worker side
def _stage_worker_main(config: StageConfig, conn_info: tuple,
                       device_env: Optional[dict]) -> None:
    """Child-process entry: env scoping → engine build → ready handshake →
    serve submit/abort/shutdown (reference: _stage_worker,
    omni_stage.py:636-733)."""
    import os

    for k, v in (device_env or {}).items():
        os.environ[k] = str(v)

    chan = _worker_channel(conn_info)
    _stage_worker_serve(config, chan)


def run_remote_stage(
    stage_configs_path: str,
    stage_id: int,
    connect: Optional[str] = None,
    discover: Optional[str] = None,
    retry_timeout: float = 120.0,
) -> None:
    """Cross-HOST stage worker entry (the serve-stage CLI): resolve the
    orchestrator's listener (explicit ``connect`` host:port, or KV-store
    ``discover``), dial with retries (the orchestrator may not be up
    yet), then serve the stage over the socket — the multi-host half of
    stage disaggregation (reference: Ray per-node stage placement,
    distributed/ray_utils/utils.py)."""
    from vllm_omni_tpu.config.stage import load_stage_configs_from_yaml

    cfgs = load_stage_configs_from_yaml(stage_configs_path)
    config = next((c for c in cfgs if c.stage_id == stage_id), None)
    if config is None:
        raise ValueError(f"no stage {stage_id} in {stage_configs_path}")
    if discover:
        from vllm_omni_tpu.distributed.multihost import (
            discover_stage_address,
        )

        connect = discover_stage_address(discover, stage_id,
                                         timeout=retry_timeout)
    if not connect:
        raise ValueError("need connect='host:port' or discover=store")
    host, _, port = connect.partition(":")
    deadline = time.monotonic() + retry_timeout
    while True:
        try:
            sock = socket.create_connection((host, int(port)),
                                            timeout=5.0)
            break
        except OSError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.5)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    # the dial timeout must NOT persist: recv() blocks for minutes while
    # the orchestrator compiles, and a lingering 5s timeout would make
    # the reader thread conclude the peer died
    sock.settimeout(None)
    # watch_parent=False: a remote worker's launcher (ssh/nohup/
    # supervisor) legitimately exits and reparents us — orchestrator
    # death shows up as socket EOF instead
    _stage_worker_serve(config, _SockChannel(sock), watch_parent=False)


def _stage_worker_serve(config: StageConfig, chan,
                        watch_parent: bool = True) -> None:
    """Engine build → ready handshake → serve loop (shared by local
    children and remote serve-stage workers).  ``watch_parent`` enables
    the getppid watchdog — only meaningful for locally-SPAWNED children
    whose parent is the orchestrator (shm rings carry no EOF)."""
    import os

    try:
        stage = OmniStage(config)
    except Exception as e:  # surface build failures to the orchestrator
        chan.send({"type": "fatal",
                   "error": f"{type(e).__name__}: {e}"})
        chan.close()
        raise
    chan.send({"type": "stage_ready", "stage_id": config.stage_id})

    inbox: queue.Queue = queue.Queue()

    def reader() -> None:
        try:
            while True:
                msg = chan.recv()
                if msg is None:
                    logger.warning("stage %d: channel EOF from "
                                   "orchestrator", config.stage_id)
                    break
                inbox.put(msg)
        except (ConnectionError, OSError) as e:
            logger.warning("stage %d: channel error: %s",
                           config.stage_id, e)
        inbox.put({"type": "shutdown"})  # orchestrator gone

    threading.Thread(target=reader, daemon=True).start()

    parent = os.getppid()
    running = True
    # redelivery dedup: a supervisor restart resubmits queued-but-
    # unstarted requests, and the at-most-once contract lives HERE — a
    # request id this worker has already accepted and not yet finished
    # is never run twice even if delivery and redelivery race.
    # Finished ids are released below: callers legitimately reuse
    # request ids across batches (Omni.generate numbers every call
    # omni-0..N), and a permanent set would silently drop the reuse —
    # and grow for the worker's lifetime.
    seen_ids: set[str] = set()
    # fresh executables when the set-up's record last rode a frame
    setup_shipped_at: Optional[int] = -1
    while running:
        if watch_parent and os.getppid() != parent:
            # orchestrator died (shm rings carry no EOF the way a socket
            # does) — exit instead of holding the chip forever
            logger.warning("stage %d: orchestrator gone; shutting down",
                           config.stage_id)
            break
        # drain commands; block briefly when idle so the loop doesn't spin
        block = not stage.has_unfinished
        while True:
            try:
                msg = inbox.get(block=block, timeout=0.05)
            except queue.Empty:
                break
            block = False
            t = msg.get("type")
            if t == "submit":
                # fault site stage{N}: one step per submit frame (e.g.
                # OMNI_TPU_FAULTS="stage1:kill_after=2" crashes this
                # worker on its second batch)
                fault_point(f"stage{config.stage_id}")
                fresh = [r for r in msg["requests"]
                         if r.request_id not in seen_ids]
                dropped = len(msg["requests"]) - len(fresh)
                if dropped:
                    logger.warning(
                        "stage %d: dropped %d duplicate request(s) "
                        "(redelivery dedup)", config.stage_id, dropped)
                seen_ids.update(r.request_id for r in fresh)
                if fresh:
                    stage.submit(fresh)
            elif t == "ping":
                # liveness heartbeat: the pong reports which requests
                # have STARTED computing (entered the running batch) so
                # a supervisor restart can redeliver the rest and fail
                # only the mid-execution ones
                started: list[str] = []
                sched = getattr(stage.engine, "scheduler", None)
                if sched is not None:
                    started = [r.request_id
                               for r in getattr(sched, "running", [])]
                try:
                    chan.send({"type": "pong", "started": started})
                except (ConnectionError, OSError, ValueError):
                    pass
            elif t == "abort":
                stage.abort(msg["request_id"])
            elif t == "profile_start":
                stage.start_profile(msg["trace_dir"])
            elif t == "profile_stop":
                stage.stop_profile()
                # ack AFTER jax flushed the trace: the orchestrator's
                # stop_profile blocks on this so callers can read the
                # trace dir (or shut down) without losing the profile
                chan.send({"type": "profile_stopped"})
            elif t == "shutdown":
                running = False
            else:
                logger.warning("stage %d: unknown message %r",
                               config.stage_id, t)
        if not running:
            break
        if stage.has_unfinished:
            try:
                outs = stage.poll()
            except Exception as e:
                chan.send({"type": "fatal",
                           "error": f"{type(e).__name__}: {e}"})
                raise
            if outs:
                # a finished id may be reused by a later batch — release
                # it from the redelivery dedup set
                seen_ids.difference_update(
                    o.request_id for o in outs if o.finished)
                # trace spans recorded in THIS process (engine + stage
                # spans) ride the outputs frame back to the orchestrator,
                # which merges them into the request's trace; the engine
                # metrics snapshot rides along so /metrics covers
                # process-disaggregated stages too, and the resilience
                # counters this WORKER accumulated (deadline kills at
                # its scheduler, faults fired here) ride the same frame
                # so the orchestrator's /metrics covers them
                from vllm_omni_tpu.resilience.metrics import (
                    resilience_metrics,
                )
                from vllm_omni_tpu.tracing import get_recorder

                msg = {"type": "outputs", "outputs": outs}
                spans = get_recorder().drain()
                if spans:
                    msg["spans"] = spans
                metrics = stage.engine_metrics_snapshot()
                if metrics:
                    # the set-up's record (the start-up spans, the
                    # executables by name: tens of KB) rides the first
                    # frame, and again only once a fresh executable
                    # was built; every other frame carries the scalars
                    compiles = (metrics.get("compile") or {}).get("compiles")
                    if compiles == setup_shipped_at:
                        metrics = _without_setup_record(metrics)
                    setup_shipped_at = compiles
                    msg["metrics"] = metrics
                resilience = resilience_metrics.snapshot()
                if resilience:
                    msg["resilience"] = resilience
                try:
                    chan.send(msg)
                except ValueError as e:
                    # frame exceeded the shm ring admission limit: tell
                    # the orchestrator with a (small) fatal message
                    chan.send({"type": "fatal",
                               "error": f"outputs too large for shm "
                                        f"transport: {e}"})
                    raise
    try:
        chan.send({"type": "bye"})
    except (ConnectionError, OSError, ValueError):
        pass
    chan.close()


# --------------------------------------------------------- orchestrator side
class ProcStage(OmniStage):
    """Orchestrator-side proxy of a stage running in a child process.

    Mirrors the in-proc OmniStage surface the orchestrator touches
    (submit / poll / has_unfinished / process_engine_inputs / stats);
    inherits the input-derivation and metrics logic, never builds a local
    engine."""

    def __init__(self, config: StageConfig,
                 device_env: Optional[dict] = None,
                 ready_timeout: float = 300.0,
                 supervised: bool = False):
        # deliberately NOT calling super().__init__ — no local engine
        self.config = config
        self.stage_id = config.stage_id
        self.tokenizer = None
        self.mm_processor = None
        self.engine = None
        self._pending: list[StageRequest] = []
        self._done: list[OmniRequestOutput] = []
        self._input_processor = config.resolve_input_processor()
        self._submit_ts: dict[str, float] = {}
        self._trace_ctx: dict[str, dict] = {}
        self.request_stats = []
        self._engine_metrics: dict = {}
        self._worker_resilience: dict = {}
        self._inflight: set[str] = set()
        self._inbox: queue.Queue = queue.Queue()
        self._fatal: Optional[str] = None
        # submit (engine loop) and profile RPC (HTTP thread) may send
        # concurrently; frames must not interleave
        self._send_lock = traced(threading.Lock(),
                                 "ProcStage._send_lock")
        self._profile_ack = threading.Event()
        # supervision (resilience/supervisor.py): a supervised stage
        # leaves in-flight requests alone when the worker dies — the
        # supervisor decides restart/redeliver/fail per request
        self._supervised = supervised
        self._device_env = device_env
        self._ready_timeout = ready_timeout
        self._remote = bool(getattr(config.runtime, "remote", False))
        # heartbeat state (ping/pong frames): last pong arrival on this
        # process's monotonic clock, and the request ids the worker
        # reported as mid-execution
        self.last_pong = time.monotonic()
        self._started_ids: set[str] = set()
        # epoch guards the reader thread across restarts: a stale
        # reader observing its (closed) channel's EOF must not latch
        # _fatal on the fresh worker
        self._epoch = 0
        self._proc = None
        self._chan = None
        self._connect_worker()

    def _connect_worker(self) -> None:
        """Spawn (or, for remote stages, await) the worker and run the
        ready handshake; called at construction and again by
        ``restart()`` after a supervised worker died."""
        config = self.config
        device_env = self._device_env
        ready_timeout = self._ready_timeout
        # transport: TCP socket (default — also works cross-host) or the
        # native shared-memory ring pair (same-host, C++ SPSC rings;
        # reference's C-backed shm MessageQueue analogue)
        transport = getattr(config.runtime, "transport", "tcp")
        if transport == "shm":
            from vllm_omni_tpu.native import ShmRing, native_available

            if not native_available():
                logger.warning(
                    "stage %d: native shm rings unavailable; "
                    "falling back to tcp", self.stage_id,
                )
                transport = "tcp"
        if transport == "shm":
            import uuid

            tag = uuid.uuid4().hex[:12]
            c2p_name = f"/omni_{tag}_c2p"
            p2c_name = f"/omni_{tag}_p2c"
            capacity = 1 << 24
            # orchestrator owns both rings (unlinked on close)
            rx = ShmRing(c2p_name, capacity=capacity, owner=True)
            tx = ShmRing(p2c_name, capacity=capacity, owner=True)
            self._chan = _ShmChannel(tx=tx, rx=rx)
            conn_info = ("shm", c2p_name, p2c_name, capacity)
            ctx = mp.get_context("spawn")
            self._proc = ctx.Process(
                target=_stage_worker_main,
                args=(config, conn_info, device_env),
                daemon=True,
            )
            try:
                _start_scoped(self._proc, device_env)
            except BaseException:
                # a spawn failure must not leak the orchestrator-owned
                # rings (closing the channel unlinks them)
                self._chan.close()
                raise
        elif transport == "tcp":
            remote = getattr(config.runtime, "remote", False)
            bind_host = (getattr(config.runtime, "bind_host", "127.0.0.1")
                         if remote else "127.0.0.1")
            bind_port = (getattr(config.runtime, "bind_port", 0)
                         if remote else 0)
            listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            listener.bind((bind_host, bind_port))
            listener.listen(1)
            if remote:
                # cross-host placement: the worker runs on ANOTHER host
                # (serve-stage CLI) and connects here; optionally publish
                # a DIALABLE address for KV-store discovery (the bind
                # address may be 0.0.0.0 or loopback — undialable from
                # the worker's host)
                self._proc = None
                port = listener.getsockname()[1]
                adv = getattr(config.runtime, "advertise_host", "")
                if not adv:
                    adv = (_primary_ip() if bind_host == "0.0.0.0"
                           else bind_host)
                addr = f"{adv}:{port}"
                discovery = getattr(config.runtime, "discovery", "")
                if discovery:
                    from vllm_omni_tpu.distributed.multihost import (
                        publish_stage_address,
                    )

                    publish_stage_address(discovery, self.stage_id, addr)
                logger.info(
                    "stage %d: waiting for REMOTE worker on %s "
                    "(serve-stage CLI on the other host)",
                    self.stage_id, addr)
            else:
                ctx = mp.get_context("spawn")
                self._proc = ctx.Process(
                    target=_stage_worker_main,
                    args=(config, ("tcp", listener.getsockname()),
                          device_env),
                    daemon=True,
                )
                _start_scoped(self._proc, device_env)
            listener.settimeout(ready_timeout)
            try:
                sock, _ = listener.accept()
            except socket.timeout:
                if self._proc is not None:
                    self._proc.terminate()
                raise TimeoutError(
                    f"stage {self.stage_id}: worker process did not "
                    f"connect within {ready_timeout}s — check the child's "
                    "device_env and engine_args (reference: stage-ready "
                    "watchdog, omni.py:352-396)"
                ) from None
            finally:
                listener.close()
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._chan = _SockChannel(sock)
        else:
            raise ValueError(f"unknown stage transport {transport!r}")
        # ready handshake: first message must be stage_ready; sliced
        # waits so a worker that dies mid-build fails fast on BOTH
        # transports (shm rings have no EOF)
        msg = None
        deadline = time.monotonic() + ready_timeout
        try:
            while time.monotonic() < deadline:
                self._chan.settimeout(2.0)
                try:
                    msg = self._chan.recv()
                    break
                except socket.timeout:
                    if self._proc is not None and not self._proc.is_alive():
                        break
            if msg is None or msg.get("type") != "stage_ready":
                err = (msg or {}).get("error",
                                      "worker hung up or timed out")
                raise RuntimeError(
                    f"stage {self.stage_id}: worker failed to become "
                    f"ready: {err}"
                )
        except BaseException:
            # every handshake-failure path must release the transport:
            # for shm the orchestrator OWNS both rings, and without the
            # close they stay linked in /dev/shm until GC happens to
            # collect this half-built stage
            if self._proc is not None:
                self._proc.terminate()
            self._chan.close()
            raise
        self._chan.settimeout(None)
        self.last_pong = time.monotonic()
        threading.Thread(target=self._reader, args=(self._epoch,),
                         daemon=True).start()

    def _reader(self, epoch: int) -> None:
        chan = self._chan
        try:
            while True:
                msg = chan.recv()
                if msg is None:
                    break
                if msg.get("type") == "profile_stopped":
                    # handled here, not in poll(): stop_profile blocks on
                    # the ack even when nothing is polling the stage
                    self._profile_ack.set()
                    continue
                if msg.get("type") == "pong":
                    # heartbeat reply; carries the mid-execution request
                    # ids so a supervisor restart knows what NOT to
                    # redeliver
                    self.last_pong = time.monotonic()
                    self._started_ids.update(msg.get("started") or ())
                    continue
                if msg.get("type") == "bye":
                    # worker's clean farewell (shutdown path): stop
                    # reading instead of parking an unhandled frame in
                    # the inbox (first omnilint OL5 harvest)
                    break
                self._inbox.put(msg)
        except (ConnectionError, OSError):
            pass
        # channel EOF is the ONLY death signal a REMOTE worker gives us
        # (self._proc is None, so poll()'s is_alive check never fires) —
        # without this, in-flight requests spin forever.  The epoch
        # check keeps a stale reader (its channel closed by restart())
        # from latching _fatal on the fresh worker.
        if (epoch == self._epoch and self._fatal is None
                and self._inflight):
            self._fatal = "worker channel closed"

    # ---------------------------------------------------------- liveness
    def _locked_send(self, frame: dict) -> None:
        """The ONE place a frame crosses the command channel.  Submit
        (engine loop), ping (heartbeat thread), and the profile/shutdown
        RPCs (server thread) all race here; interleaved writes would
        corrupt the pickle stream, so the send lock is held ACROSS the
        write — that is the lock's whole contract, not an accident."""
        with self._send_lock:
            # omnilint: disable=OL9 - the send lock IS the frame
            # serializer; holding it across the pipe write is the point
            self._chan.send(frame)

    def ping(self) -> bool:
        """Send a liveness heartbeat; the worker replies with a ``pong``
        frame (handled in ``_reader``).  Returns False when the channel
        is already known-dead."""
        if self._fatal is not None:
            return False
        try:
            self._locked_send({"type": "ping"})
            return True
        except (ConnectionError, OSError, ValueError) as e:
            self._fatal = f"ping failed: {type(e).__name__}: {e}"
            return False

    def mark_hung(self, reason: str) -> None:
        """Declare the worker dead (e.g. heartbeat misses exhausted):
        latch the fatal reason and reap the process so restart() can
        respawn cleanly."""
        if self._fatal is None:
            self._fatal = reason
        if self._proc is not None and self._proc.is_alive():
            self._proc.terminate()

    @property
    def restartable(self) -> bool:
        """Only locally-spawned workers can be restarted — a remote
        worker's lifecycle belongs to its own host's launcher."""
        return not self._remote

    @property
    def started_request_ids(self) -> set[str]:
        """Requests the worker last reported as mid-execution (from the
        heartbeat pong) — still in flight here."""
        return self._started_ids & self._inflight

    def restart(self) -> None:
        """Respawn the worker after a crash/hang (supervised stages).
        The caller (StageSupervisor) owns redelivery; this only rebuilds
        the transport + process and clears the fatal latch."""
        if not self.restartable:
            raise RuntimeError(
                f"stage {self.stage_id}: remote workers cannot be "
                "restarted by the orchestrator")
        self._epoch += 1  # detach the old reader before closing its chan
        if self._chan is not None:
            self._chan.close()
        if self._proc is not None:
            if self._proc.is_alive():
                self._proc.terminate()
            self._proc.join(5.0)
        # drop frames from the dead worker: outputs for requests the
        # supervisor is about to fail/redeliver must not resurface
        while True:
            try:
                self._inbox.get_nowait()
            except queue.Empty:
                break
        self._started_ids.clear()
        self._profile_ack.set()  # never leave a stop_profile waiter hung
        self._connect_worker()
        self._fatal = None

    # ------------------------------------------------------------- intake
    def submit(self, reqs: list[StageRequest]) -> None:
        now = time.perf_counter()
        for r in reqs:
            self._submit_ts[r.request_id] = now
            if r.trace:
                self._trace_ctx[r.request_id] = r.trace
            self._inflight.add(r.request_id)
            # a stage in a process of its own keeps one output a request:
            # partial outputs would each cross the channel (docs/serving.md)
            r.stream = False
        if self._fatal is None:
            try:
                self._locked_send({"type": "submit", "requests": reqs})
            except (ConnectionError, OSError, ValueError) as e:
                # worker died between batches: the next poll() converts
                # the whole in-flight set to per-request error outputs —
                # never abort batch-mates on healthy stages by raising.
                # Keep the exception TYPE: a bare OSError often has an
                # empty str(), and per-request error outputs must say
                # why the worker was lost, not just that it was.
                self._fatal = (f"submit failed: "
                               f"{type(e).__name__}: {e}".rstrip(": "))

    def abort(self, request_id: str) -> None:
        """``OmniStage.abort`` through the channel: the worker's engine
        frees the request and no output of it follows."""
        if request_id not in self._inflight:
            return
        self._inflight.discard(request_id)
        self._started_ids.discard(request_id)
        self._submit_ts.pop(request_id, None)
        self._trace_ctx.pop(request_id, None)
        if self._fatal is None:
            try:
                self._locked_send({"type": "abort",
                                   "request_id": request_id})
            except (ConnectionError, OSError, ValueError):
                pass  # a dead worker holds nothing to free

    # -------------------------------------------------------------- drive
    def poll(self) -> list[OmniRequestOutput]:
        outs: list[OmniRequestOutput] = []
        while True:
            try:
                msg = self._inbox.get_nowait()
            except queue.Empty:
                break
            t = msg.get("type")
            if t == "outputs":
                outs.extend(msg["outputs"])
                spans = msg.get("spans")
                if spans:
                    # merge worker-side spans into this process's trace
                    from vllm_omni_tpu.tracing import get_recorder

                    get_recorder().extend(spans)
                metrics = msg.get("metrics")
                if metrics:
                    self._engine_metrics = _with_setup_record(
                        metrics, self._engine_metrics)
                resilience = msg.get("resilience")
                if resilience:
                    # latest worker-lifetime resilience counters; merged
                    # into /metrics by prometheus.render_from_omni
                    self._worker_resilience = resilience
            elif t == "fatal":
                self._fatal = msg.get("error", "unknown")
        for o in outs:
            if o.finished:
                self._inflight.discard(o.request_id)
                self._started_ids.discard(o.request_id)
            self._record(o)
        if self._inflight and self._fatal is None \
                and self._proc is not None and not self._proc.is_alive():
            self._fatal = f"worker exited (code {self._proc.exitcode})"
        if self._supervised:
            # the supervisor owns the failure policy (restart, redeliver
            # unstarted, fail mid-execution as retryable) — never mass-
            # fail the in-flight set here
            return outs
        if self._inflight and self._fatal is not None:
            # fail every in-flight request on this stage; the pipeline
            # keeps serving requests on healthy stages
            logger.error("stage %d worker died: %s",
                         self.stage_id, self._fatal)
            for rid in sorted(self._inflight):
                o = OmniRequestOutput.from_error(
                    rid, f"stage worker died: {self._fatal}",
                    stage_id=self.stage_id)
                self._record(o)
                outs.append(o)
            self._inflight.clear()
        return outs

    @property
    def has_unfinished(self) -> bool:
        return bool(self._inflight)

    def engine_metrics_snapshot(self) -> dict:
        """Last engine snapshot shipped by the worker (rides the outputs
        frames) — the cross-process face of OmniStage's live snapshot."""
        return self._engine_metrics

    def resilience_snapshot(self) -> dict:
        """Last resilience-counter snapshot shipped by the worker
        (deadline kills at its scheduler, faults fired in its process);
        counts cover the CURRENT worker's lifetime — a restart resets
        them, which Prometheus counter semantics tolerate."""
        return self._worker_resilience

    # ----------------------------------------------------------- profiling
    def start_profile(self, trace_dir: str) -> None:
        """Profiling must run in the worker process (it owns the devices):
        ship the command over the socket (reference: PROFILER_* tasks).
        A dead worker is a logged no-op, never an exception — one broken
        stage must not abort the fan-out over healthy ones."""
        if self._fatal is not None:
            logger.warning("stage %d: skip profile_start (worker dead)",
                           self.stage_id)
            return
        try:
            self._locked_send({"type": "profile_start",
                               "trace_dir": trace_dir})
        except (ConnectionError, OSError) as e:
            self._fatal = f"profile_start failed: {e}"

    def stop_profile(self, timeout: float = 60.0, wait: bool = True) -> None:
        """Blocks until the worker acked the stop (the trace file is
        flushed by then) or ``timeout`` passes; ``wait=False`` lets a
        multi-stage fan-out send every stop first and then wait on all
        acks concurrently (bounding worst-case latency at one timeout)."""
        if self._fatal is not None:
            return
        self._profile_ack.clear()
        try:
            self._locked_send({"type": "profile_stop"})
        except (ConnectionError, OSError) as e:
            self._fatal = f"profile_stop failed: {e}"
            return
        if wait:
            self.wait_profile_ack(timeout)

    def wait_profile_ack(self, timeout: float = 60.0) -> None:
        if self._fatal is not None:
            return
        if not self._profile_ack.wait(timeout):
            logger.warning(
                "stage %d: no profile_stop ack within %.0fs (long step "
                "in flight?) — trace may be incomplete",
                self.stage_id, timeout,
            )

    # ----------------------------------------------------------- shutdown
    def shutdown(self, timeout: float = 10.0) -> None:
        try:
            self._locked_send({"type": "shutdown"})
        except (ConnectionError, OSError):
            pass
        if self._proc is not None:
            self._proc.join(timeout)
            if self._proc.is_alive():
                self._proc.terminate()
                self._proc.join(5.0)
        self._chan.close()

    def __del__(self) -> None:
        try:
            if getattr(self, "_proc", None) is not None \
                    and self._proc.is_alive():
                self._proc.terminate()
        except Exception:
            pass


# ------------------------------------------------ the set-up's record, once
def _without_setup_record(snap: dict) -> dict:
    """An engine snapshot less what does not change between two fresh
    executables: ``startup`` and ``compile.executables``."""
    snap = {k: v for k, v in snap.items() if k != "startup"}
    if "compile" in snap:
        snap["compile"] = {k: v for k, v in snap["compile"].items()
                           if k != "executables"}
    return snap


def _with_setup_record(snap: dict, last: dict) -> dict:
    """``snap`` as the worker holds it: what ``_without_setup_record``
    took out comes from ``last``, the snapshot kept so far."""
    if "startup" not in snap and "startup" in last:
        snap = {**snap, "startup": last["startup"]}
    kept = (last.get("compile") or {}).get("executables")
    if kept is not None and "executables" not in snap.get("compile", {}):
        snap = {**snap, "compile": {**snap.get("compile", {}),
                                    "executables": kept}}
    return snap
