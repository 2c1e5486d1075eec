"""Ordering-log seam + partition leases: multi-node coordination.

Two abstractions the reference keeps in `services-core`
(server/routerlicious/packages/services-core/src/queue.ts `IProducer`/
`IConsumer`) and ZooKeeper (partition ownership for the Kafka
consumers, SURVEY.md §2.5 ⚙️):

- **Producer/consumer seam** — lambdas talk to topics only through
  `Producer`/`Consumer`; the in-proc journal (`server.log.MessageLog`)
  is one backend, and `SharedFileTopic` is a CROSS-PROCESS backend
  (multi-writer appends under an OS file lock, consumers tail from a
  checkpointed offset), so two server processes share one ordering
  log the way two routerlicious pods share a Kafka cluster.
- **Lease manager** — partition ownership with expiry-based failover
  (the zookeeper role): a worker acquires leases over document-space
  partitions, renews them while alive, and a peer takes over any
  lease that expires (crashed owner), resuming from the dead worker's
  checkpointed consumer offset.

`tools/partition_worker_main.py` runs a sequencer worker over this
seam; `tests/test_partition_leases.py` kills one of two workers and
proves the survivor takes over its partitions exactly-once.
"""

from __future__ import annotations

import contextlib
import errno
import json
import os
import random
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, \
    Protocol, Tuple

from ..utils.metrics import get_registry, span


class FencedError(RuntimeError):
    """A write carried a fencing token older than (or tied with but not
    bound to) the one the target has already accepted: the writer is a
    deposed owner and its write must not land (the zookeeper/Kafka
    fencing contract — exactly-once rests on deposed owners being
    REJECTED at the write path, not merely asked to stand down)."""


def _check_fence(cur_fence: int, cur_owner: Optional[str],
                 fence: int, owner: Optional[str], what: str) -> None:
    """THE fence-gate rule, shared by every fenced write path: reject a
    fence below the highest accepted, or an equal fence from a
    different owner than the one it first bound to (ties broken by
    first binder — the guard for the pathological same-fence split)."""
    if fence < cur_fence or (fence == cur_fence and cur_owner is not None
                             and owner != cur_owner):
        raise FencedError(
            f"{what}: fence {fence} ({owner}) rejected; already bound "
            f"to {cur_fence} ({cur_owner})"
        )


@contextlib.contextmanager
def flock_exclusive(f, lock_timeout_s: Optional[float],
                    path: str) -> Iterator[None]:
    """Exclusive flock on `f` for one append critical section. With a
    timeout, acquisition is bounded (LOCK_NB polling) so a takeover
    successor never wedges behind a stalled — e.g. SIGSTOPped —
    writer's lock: it times out, has the zombie killed (the
    supervisor's stale-heartbeat role), and retries. Shared by every
    topic flavor so the takeover protocol cannot fork."""
    import fcntl

    if lock_timeout_s is None:
        fcntl.flock(f.fileno(), fcntl.LOCK_EX)
    else:
        deadline = time.time() + lock_timeout_s
        while True:
            try:
                fcntl.flock(f.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
                break
            except OSError:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"append lock on {path} held past "
                        f"{lock_timeout_s}s"
                    )
                time.sleep(0.005)
    try:
        yield
    finally:
        fcntl.flock(f.fileno(), fcntl.LOCK_UN)


# ---------------------------------------------------------------------------
# storage-fault seam (the chaos harness's disk fault class)
# ---------------------------------------------------------------------------

# Path of a JSON fault-spec file; when set, every durable write path
# (topic append, checkpoint save) consults it right before fsync. The
# chaos harness points CHILD processes at a spec it toggles mid-run:
#   {"mode": "enospc"}                -> the write raises OSError(ENOSPC)
#   {"mode": "stall", "stall_s": S}   -> the fsync stalls S seconds
#   optional "kinds": ["topic", ...]  -> restrict to those write paths
# Unset (production) the check is a single dict lookup.
DISK_FAULT_ENV = "FLUID_DISK_FAULT"


def check_disk_fault(kind: str) -> None:
    """Injection point for the storage failure classes a real deli farm
    meets (volume full, device write stall): called with the write
    about to go durable, so an injected ENOSPC aborts BEFORE bytes land
    — exactly where the real error surfaces — and a stall sits where a
    slow fsync would."""
    spec_path = os.environ.get(DISK_FAULT_ENV)
    if not spec_path:
        return
    try:
        with open(spec_path) as f:
            spec = json.load(f)
    except (OSError, ValueError):
        return  # no/garbled spec: no fault
    if not isinstance(spec, dict):
        return
    kinds = spec.get("kinds")
    if kinds and kind not in kinds:
        return
    mode = spec.get("mode")
    if mode == "enospc":
        raise OSError(errno.ENOSPC, f"injected ENOSPC on {kind} write")
    if mode == "stall":
        time.sleep(float(spec.get("stall_s", 0.25)))


def retry_durable(fn: Callable[[], Any], attempts: int = 8,
                  base_s: float = 0.02, cap_s: float = 0.5,
                  on_retry: Optional[Callable[[int, BaseException, float],
                                              None]] = None) -> Any:
    """Bounded-retry jittered backoff for DURABLE writes (topic append,
    checkpoint save) under transient storage failure — ENOSPC, EIO, a
    stalled volume. Graceful degradation, not masking: `on_retry` fires
    per attempt so the caller can flag itself degraded (heartbeat,
    metrics) while it waits, and once the budget is spent the error
    surfaces (hard-fail — the supervisor's restart is the next line of
    defense). `FencedError` is a RuntimeError, not an OSError, so a
    deposed writer is never retried back to life."""
    k = 0
    while True:
        try:
            return fn()
        except OSError as exc:
            if k >= attempts - 1:
                raise
            delay = min(cap_s, base_s * (1 << k))
            delay *= 0.5 + random.random() * 0.5  # jitter: desync peers
            if on_retry is not None:
                on_retry(k, exc, delay)
            time.sleep(delay)
            k += 1


class Producer(Protocol):
    """services-core/src/queue.ts IProducer role."""

    def send(self, message: Any) -> int: ...


class Consumer(Protocol):
    """services-core/src/queue.ts IConsumer role: an offset-owning
    reader whose position is the caller's checkpoint state."""

    offset: int

    def poll(self, max_count: Optional[int] = None) -> List[Any]: ...


class JournalProducer:
    """Producer over an in-proc `server.log.LogTopic`."""

    def __init__(self, topic):
        self.topic = topic

    def send(self, message: Any) -> int:
        return self.topic.append(message)


class JournalConsumer:
    """Consumer over an in-proc `server.log.LogTopic`."""

    def __init__(self, topic, offset: int = 0):
        self.topic = topic
        self.offset = offset

    def poll(self, max_count: Optional[int] = None) -> List[Any]:
        msgs = self.topic.read(self.offset, max_count)
        self.offset += len(msgs)
        return msgs


def fsync_file(f, kind: str = "topic") -> None:
    """One counted fsync: every durable-write fsync on the hot path
    routes through here so `topic_fsyncs_total{kind=}` reports the
    per-record durability floor the fused hop lowers."""
    os.fsync(f.fileno())
    from ..utils.metrics import get_registry

    get_registry().counter("topic_fsyncs_total", kind=kind).inc()


class SharedFileTopic:
    """A cross-process topic over one JSONL file.

    Appends take an OS file lock (multi-writer safe); consumers tail
    the file from a LINE offset, re-reading anything new on each poll
    — the minimal faithful form of a shared Kafka partition. Entries
    are plain JSON values.

    Robustness contract (the chaos-harness substrate):

    - **Torn tail** — a reader never consumes a final line lacking its
      trailing newline (an append in progress, or a writer that died
      mid-write); the line is re-read complete on the next poll. The
      next append SEALS a crash-torn tail with a newline first, so the
      junk remnant becomes one unparseable line instead of corrupting
      the following record; readers skip (but still count) lines that
      fail to parse.
    - **Fencing** — appends may carry a ``fence`` token (+ owner). The
      topic remembers the highest accepted (fence, owner) in a sidecar
      file, updated under the same append lock; a lower fence — or an
      equal fence from a different owner than the one it first bound
      to — raises :class:`FencedError` and writes nothing. This is
      what makes a deposed lease holder's post-takeover writes
      *demonstrably rejected* rather than merely discouraged.
    """

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        if not os.path.exists(path):
            with open(path, "a"):
                pass
        # Doorbell producer state: cached write fds into registered
        # consumer bells (re-listed per ring — see _ring_doorbells).
        self._bell_wfds: Dict[str, int] = {}

    def __del__(self):
        # Short-lived topic objects (probes, one-shot appenders) must
        # not leak the ring fds they cached.
        for fd in (getattr(self, "_bell_wfds", None) or {}).values():
            try:
                os.close(fd)
            except Exception:
                pass

    # -------------------------------------------------------- doorbells

    def _ring_doorbells(self) -> None:
        """Wake every doorbell-registered consumer of this topic (one
        byte per bell). Costs a single failed stat when no consumer
        ever registered; rings AFTER the append went durable, outside
        the append lock, so waking consumers never contend with the
        writer. Purely advisory — any failure here degrades to the
        consumer's bounded-timeout poll."""
        d = self.path + ".bells"
        try:
            names = {n for n in os.listdir(d) if not n.startswith(".")}
        except OSError:
            return  # no consumer ever registered: one failed syscall
        # Re-list per ring rather than caching on the dir mtime: write
        # fds are still cached (the per-ring cost is one listdir next
        # to an append that already paid open+flock+fsync), but
        # DISCOVERY never trusts directory attributes — network/
        # passthrough filesystems (v9fs CI containers) cache those
        # across processes, and a bell registered after the first scan
        # would stay invisible to the ringer forever.
        cache = self._bell_wfds
        for name in list(cache):
            if name not in names:
                try:
                    os.close(cache.pop(name))
                except OSError:
                    cache.pop(name, None)
        for name in names:
            if name in cache:
                continue
            try:
                cache[name] = os.open(
                    os.path.join(d, name),
                    os.O_WRONLY | os.O_NONBLOCK,
                )
            except OSError as exc:
                # ENXIO alone means "no live reader" — the consumer
                # died (its O_RDWR fd vanished with it); reap the bell
                # so a churned farm can't accumulate dead FIFOs. Any
                # OTHER error (EMFILE, EACCES...) is a PRODUCER-side
                # problem: unlinking would permanently sever a live
                # consumer with no re-registration path — leave it for
                # a later ring to open.
                if exc.errno == errno.ENXIO:
                    try:
                        os.unlink(os.path.join(d, name))
                    except OSError:
                        pass
        for name, fd in list(cache.items()):
            try:
                os.write(fd, b"!")
            except BlockingIOError:
                pass  # pipe full: a wake is already pending
            except OSError:
                # Reader went away since we opened (EPIPE): drop the
                # fd; the next ring's listing reaps the file.
                try:
                    os.close(fd)
                except OSError:
                    pass
                cache.pop(name, None)

    # ------------------------------------------------------------ fence

    def _fence_path(self) -> str:
        return self.path + ".fence"

    def latest_fence(self) -> Tuple[int, Optional[str]]:
        """The highest (fence, owner) this topic has accepted."""
        try:
            with open(self._fence_path()) as f:
                d = json.load(f)
            return int(d.get("fence", 0)), d.get("owner")
        except (OSError, ValueError):
            return 0, None

    def _gate_fence(self, fence: Optional[int],
                    owner: Optional[str]) -> None:
        """Check-and-advance the fence sidecar. Caller holds the
        append lock, so read-modify-write is race-free."""
        if fence is None:
            return
        cur, cur_owner = self.latest_fence()
        _check_fence(cur, cur_owner, fence, owner,
                     os.path.basename(self.path))
        if fence > cur or cur_owner is None:
            tmp = self._fence_path() + f".tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"fence": fence, "owner": owner}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._fence_path())

    # ----------------------------------------------------------- append

    def append(self, message: Any, fence: Optional[int] = None,
               owner: Optional[str] = None) -> int:
        return self.append_many([message], fence=fence, owner=owner)

    def append_many(self, messages: List[Any],
                    fence: Optional[int] = None,
                    owner: Optional[str] = None,
                    lock_timeout_s: Optional[float] = None,
                    fsync: bool = True) -> int:
        """Append a batch under the OS lock; returns the payload bytes
        written (the byte-based checkpoint-cadence signal).

        ``fsync=False`` skips the data fsync: the append is ordered
        and torn-tail-safe (readers never consume an incomplete line)
        but not crash-durable — for DERIVED feeds whose records are
        deterministically regenerable from an upstream durable topic
        (the fused hop's broadcast leg), where exactly-once recovery
        re-emits anything the page cache lost."""
        # An empty batch still gates: a deposed owner must learn it is
        # deposed even when it has nothing to write.
        payload = b"".join(
            json.dumps(m).encode() + b"\n" for m in messages
        )
        with open(self.path, "r+b") as f:
            with flock_exclusive(f, lock_timeout_s, self.path):
                self._gate_fence(fence, owner)
                f.seek(0, os.SEEK_END)
                pos = f.tell()
                if pos > 0:
                    f.seek(pos - 1)
                    if f.read(1) != b"\n":
                        # A writer died mid-append: seal its torn line
                        # so our record starts on a fresh line and the
                        # remnant parses (and is skipped) as one junk
                        # line.
                        f.write(b"\n")
                check_disk_fault("topic")
                f.write(payload)
                f.flush()
                if fsync:
                    fsync_file(f, "topic")
        if messages:
            self._ring_doorbells()
        return len(payload)

    # ------------------------------------------------------------- read

    def read_entries(self, offset: int,
                     max_count: Optional[int] = None
                     ) -> Tuple[List[Tuple[int, Any]], int]:
        """Parse lines from line-index `offset`. Returns
        ``([(line_index, value), ...], next_offset)``.

        A final line without a trailing newline is NOT consumed (it is
        an append in progress — complete on the next poll); a complete
        line that fails to parse (sealed torn remnant) is skipped but
        still counted, so offsets stay stable across all readers.

        `max_count` caps the PARSED entries taken (micro-batch bound:
        consumers yield between batches instead of swallowing a whole
        backlog); next_offset then resumes right after the last entry
        taken, with skipped junk lines staying counted."""
        with open(self.path, "rb") as f:
            data = f.read()
        if not data:
            return [], offset
        lines = data.split(b"\n")
        # Drop the final element either way: it is the '' split
        # artifact of a newline-terminated file, or a torn
        # (in-progress) line that must be re-read complete next poll.
        lines.pop()
        out: List[Tuple[int, Any]] = []
        for i in range(offset, len(lines)):
            if max_count is not None and len(out) >= max_count:
                return out, (out[-1][0] + 1 if out else offset)
            line = lines[i].strip()
            if not line:
                continue
            try:
                out.append((i, json.loads(line)))
            except ValueError:
                continue  # sealed junk from a crashed writer
        return out, max(offset, len(lines))

    def read_from(self, offset: int) -> List[Any]:
        return [v for _, v in self.read_entries(offset)[0]]


# ---------------------------------------------------------------------------
# topic doorbells (event-driven new-records wakeup)
# ---------------------------------------------------------------------------

# Kill switch: FLUID_DOORBELL=0 keeps every consumer on the pure poll
# loop (the baseline the doorbells are compared with; also the escape
# hatch on a platform where FIFOs misbehave).
DOORBELL_ENV = "FLUID_DOORBELL"

_bell_seq = 0


def doorbells_enabled() -> bool:
    """Whether event-driven topic wakeups are available AND wanted.
    Doorbells are advisory only — with them off (or unsupported: no
    ``os.mkfifo``), every consumer falls back to the bounded-timeout
    poll loop it always had, so fencing/torn-read semantics never
    depend on this answer."""
    return (os.environ.get(DOORBELL_ENV, "1").lower()
            not in ("0", "off", "no")
            and hasattr(os, "mkfifo"))


class TopicDoorbell:
    """One consumer's wakeup line for one topic.

    A FIFO under ``<topic path>.bells/``: `append_many` writes one
    byte into every registered bell after its records are durable, and
    the consumer waits on its bell with a BOUNDED timeout — so the
    idle-poll interval stack that dominates low-load end-to-end
    latency collapses to an event wake, while the timeout keeps poll
    as the correctness fallback (a bell rung between the consumer's
    empty poll and its wait, a lost FIFO, a disabled platform: all
    degrade to exactly the old behavior).

    The consumer holds the FIFO open O_RDWR (nonblocking): the
    always-present reader means a producer's O_WRONLY|O_NONBLOCK open
    succeeds while the consumer lives (ENXIO = consumer died, the
    producer garbage-collects the bell), and the always-present writer
    means the read end never signals EOF-readable to select — no busy
    wake. The FIFO is created under a dot-name and renamed into place
    only after the read end is open, so a producer can never observe a
    bell without a live reader and wrongly reap it."""

    def __init__(self, topic_path: str):
        global _bell_seq
        self.dir = topic_path + ".bells"
        os.makedirs(self.dir, exist_ok=True)
        _bell_seq += 1
        name = f"{os.getpid()}-{_bell_seq}.bell"
        tmp = os.path.join(self.dir, f".{name}.tmp")
        self.path = os.path.join(self.dir, name)
        os.mkfifo(tmp)
        self._fd = os.open(tmp, os.O_RDWR | os.O_NONBLOCK)
        os.rename(tmp, self.path)

    def fileno(self) -> int:
        return self._fd

    def drain(self) -> bool:
        """Consume pending ring bytes; True iff any were pending."""
        rang = False
        try:
            while os.read(self._fd, 4096):
                rang = True
        except (BlockingIOError, OSError):
            pass
        return rang

    def wait(self, timeout_s: float) -> bool:
        return wait_doorbells([self], timeout_s)

    def close(self) -> None:
        if self._fd is not None:
            try:
                os.close(self._fd)
            except OSError:
                pass
            self._fd = None
        try:
            os.unlink(self.path)
        except OSError:
            pass


def wait_doorbells(bells: List["TopicDoorbell"],
                   timeout_s: float) -> bool:
    """Sleep until ANY of `bells` rings or `timeout_s` elapses (the
    poll fallback); returns whether a ring woke us. Rings that arrived
    while the consumer was busy processing are still pending in the
    pipe, so the next wait returns immediately — a wakeup is never
    lost, only (harmlessly) early."""
    import select

    fds = [b._fd for b in bells if b is not None and b._fd is not None]
    if not fds:
        time.sleep(timeout_s)
        return False
    try:
        ready, _, _ = select.select(fds, [], [], timeout_s)
    except OSError:
        time.sleep(timeout_s)
        return False
    if not ready:
        return False
    for b in bells:
        if b is not None and b._fd in ready:
            b.drain()
    return True


class TailReader:
    """Incremental reader over a `SharedFileTopic`: remembers the byte
    position of the last fully-consumed line, so each poll reads only
    NEW bytes instead of re-reading (and re-splitting) the whole file —
    `read_entries` is O(file) per call, which makes a long-lived
    consumer O(file²) over its lifetime; the lambda roles and the
    pipeline bench tail topics through this instead.

    Same robustness contract as `read_entries`: a final line without
    its trailing newline is not consumed (byte position stays before
    it), junk lines are skipped but still counted, and line indices
    (`next_line`) stay identical to `read_entries` offsets — so
    checkpointed line offsets and `inOff` bookkeeping are unchanged."""

    def __init__(self, topic: SharedFileTopic, line_offset: int = 0):
        self.topic = topic
        self.next_line = line_offset
        self._pos = 0
        # Lines the caller's offset is AHEAD of the file (a checkpoint
        # taken against a longer topic): consumed silently as they
        # appear, never delivered — matching read_entries(offset),
        # which returns nothing below the requested offset.
        self._behind = 0
        if line_offset > 0:
            # One O(file) skip to translate the line offset into a byte
            # position; everything after is incremental.
            with open(topic.path, "rb") as f:
                data = f.read()
            lines = data.split(b"\n")
            lines.pop()
            take = min(line_offset, len(lines))
            self._pos = sum(len(l) + 1 for l in lines[:take])
            self._behind = line_offset - take

    def poll(self, max_count: Optional[int] = None
             ) -> List[Tuple[int, Any]]:
        """Parse up to `max_count` new complete entries; returns
        [(line_index, value), ...] and advances past them."""
        with open(self.topic.path, "rb") as f:
            f.seek(self._pos)
            data = f.read()
        if not data:
            return []
        end = data.rfind(b"\n")
        if end < 0:
            return []  # torn tail only: re-read complete next poll
        lines = data[:end].split(b"\n")
        out: List[Tuple[int, Any]] = []
        pos = self._pos
        line_no = self.next_line
        loads = json.loads
        for raw in lines:
            if self._behind:
                # Below the requested offset: swallow without delivery
                # (next_line already accounts for these lines).
                self._behind -= 1
                pos += len(raw) + 1
                continue
            if max_count is not None and len(out) >= max_count:
                break
            pos += len(raw) + 1
            line = raw.strip()
            if line:
                try:
                    out.append((line_no, loads(line)))
                except ValueError:
                    pass  # sealed junk from a crashed writer
            line_no += 1
        self._pos = pos
        self.next_line = line_no
        return out


class SharedFileProducer:
    def __init__(self, topic: SharedFileTopic):
        self.topic = topic

    def send(self, message: Any) -> int:
        self.topic.append(message)
        return -1  # offsets are consumer-side for file topics


class SharedFileConsumer:
    def __init__(self, topic: SharedFileTopic, offset: int = 0):
        self.topic = topic
        self.offset = offset

    def poll(self, max_count: Optional[int] = None) -> List[Any]:
        # The cap threads into the read itself (micro-batch bound);
        # max_count=0 takes nothing and leaves the offset alone.
        entries, next_offset = self.topic.read_entries(self.offset, max_count)
        self.offset = next_offset
        return [v for _, v in entries]


# ---------------------------------------------------------------------------
# Lease manager (zookeeper role)
# ---------------------------------------------------------------------------


class _ClaimBusy(Exception):
    """Another worker holds the arbitration claim right now."""


class LeaseManager:
    """Expiry-based partition leases over a shared directory.

    A lease is a JSON file `<dir>/<partition>.lease` holding
    ``{"owner", "expires", "fence"}``. All mutations — acquire, renew,
    release — are arbitrated under an ``O_CREAT|O_EXCL`` claim file
    (`<partition>.lease.claim`): exactly one worker can create it, so
    the read-decide-write sequence is a critical section and two
    workers racing for an expired lease can no longer both "win" with
    the same fence (the round-5 ADVICE.md medium race — the old
    read-back arbitration let racer A read back before racer B renamed
    over it).

    Fences are allocated from a monotonic counter file
    (`<partition>.lease.fencecounter`) updated only inside the claim,
    so every ownership change gets a strictly larger token even if the
    lease file itself is deleted. Claimant liveness is carried by a
    kernel flock held on the claim fd for the whole critical section:
    a crashed claimant's claim is broken immediately (its lock died
    with it), while a live-but-stalled claimant's claim is never
    broken — so two arbitrators can't coexist and fences can't split.
    Belt-and-braces, the WRITE path still enforces tokens: fenced
    topics/checkpoints bind each fence value to the first owner that
    uses it and reject any other (`SharedFileTopic` appends /
    `FencedCheckpointStore.save` raise `FencedError`).
    """

    def __init__(self, directory: str, owner: str, ttl_s: float = 2.0,
                 claim_ttl_s: float = 1.0,
                 fence_scope: Optional[str] = None):
        """`fence_scope` names a SHARED monotonic fence counter all of
        this manager's partitions allocate from (file
        ``<dir>/<scope>.fencecounter``) instead of the default
        per-partition counter. The elastic fabric needs it: after a
        range split/merge the successor binds its fence on the
        PREDECESSOR's topics, so fences must be comparable across
        lease keys — one fabric-wide counter makes every ownership
        change anywhere strictly newer than everything before it."""
        self.dir = directory
        self.owner = owner
        self.ttl_s = ttl_s
        self.claim_ttl_s = claim_ttl_s
        self.fence_scope = fence_scope
        os.makedirs(directory, exist_ok=True)

    def _path(self, partition: str) -> str:
        return os.path.join(self.dir, f"{partition}.lease")

    def _read(self, partition: str) -> Optional[dict]:
        try:
            with open(self._path(partition)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _write(self, partition: str, lease: dict) -> None:
        tmp = self._path(partition) + f".tmp.{self.owner}.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(lease, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._path(partition))

    # -------------------------------------------------------- the claim

    @contextlib.contextmanager
    def _claim(self, partition: str,
               timeout_s: Optional[float] = None) -> Iterator[None]:
        """O_CREAT|O_EXCL mutual exclusion for lease arbitration.
        Raises `_ClaimBusy` if the claim stays foreign past
        `timeout_s` (default: claim_ttl_s).

        Holder liveness is probed through a kernel flock the claimant
        holds on its claim fd for the whole critical section: a dead
        claimant's lock vanishes with the process (so its claim is
        safely broken by whichever single breaker wins the lock), a
        live-but-stopped claimant's lock persists (so its claim is
        NEVER broken and the two-winners split cannot happen, unlike
        mtime-staleness breaking). It also makes release trivially
        safe: our claim can only have been broken if this process
        died, so the final unlink is always our own file."""
        import fcntl

        path = self._path(partition) + ".claim"
        deadline = time.time() + (
            self.claim_ttl_s if timeout_s is None else timeout_s
        )
        fd: Optional[int] = None
        while True:
            try:
                fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR)
            except FileExistsError:
                try:
                    probe = os.open(path, os.O_RDWR)
                except OSError:
                    continue  # released between EEXIST and open; retry
                try:
                    try:
                        fcntl.flock(probe, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    except OSError:
                        # A live holder (possibly stopped) — wait.
                        if time.time() > deadline:
                            raise _ClaimBusy(partition)
                        time.sleep(0.002)
                        continue
                    # Lock acquired: the holder died before releasing.
                    # Racing breakers serialize on this lock, and while
                    # we hold it no new claimant can unlink the path,
                    # so break it only if it still names our inode.
                    try:
                        if os.stat(path).st_ino == os.fstat(probe).st_ino:
                            os.unlink(path)
                    except OSError:
                        pass
                finally:
                    os.close(probe)
                continue
            try:
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                # A prober grabbed the lock on our just-created claim
                # before we could (and will break it as holderless).
                # Stand down and retry.
                os.close(fd)
                fd = None
                if time.time() > deadline:
                    raise _ClaimBusy(partition)
                continue
            # Close the create→flock window: a breaker that saw our
            # claim unlocked may have unlinked it; if the path no
            # longer names our inode, stand down and retry.
            try:
                same = os.stat(path).st_ino == os.fstat(fd).st_ino
            except OSError:
                same = False
            if not same:
                os.close(fd)
                fd = None
                continue
            break
        try:
            os.write(fd, f"{self.owner} {os.getpid()}".encode())
            yield
        finally:
            try:
                os.unlink(path)
            except OSError:
                pass
            os.close(fd)  # releases the liveness lock

    def _next_fence(self, partition: str, cur: Optional[dict]) -> int:
        """Allocate the next fencing token from the monotonic counter
        (called only inside the claim). max() with the lease's own
        fence heals a lost/stale counter file.

        A scoped (shared) counter is serialized by its own flock: the
        per-partition claim no longer covers it, and two DIFFERENT
        keys' claims racing the read-modify-write could mint TIED
        fences — which the write-path tie rule would then reject for
        whichever owner binds second, livelocking a legitimate
        successor (the split-children race)."""
        if self.fence_scope is not None:
            cpath = os.path.join(
                self.dir, f"{self.fence_scope}.fencecounter"
            )
            lock = open(cpath + ".lock", "a+")
        else:
            cpath = self._path(partition) + ".fencecounter"
            lock = None
        try:
            if lock is not None:
                with flock_exclusive(lock, None, cpath):
                    return self._bump_fence(cpath, cur)
            return self._bump_fence(cpath, cur)
        finally:
            if lock is not None:
                lock.close()

    def _bump_fence(self, cpath: str, cur: Optional[dict]) -> int:
        try:
            with open(cpath) as f:
                counter = int(f.read().strip() or 0)
        except (OSError, ValueError):
            counter = 0
        fence = max(counter, int(cur.get("fence", 0)) if cur else 0) + 1
        tmp = cpath + f".tmp.{self.owner}.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(str(fence))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, cpath)
        return fence

    # ------------------------------------------------------- operations

    def try_acquire(self, partition: str,
                    now: Optional[float] = None) -> Optional[int]:
        """Acquire `partition` if unowned, expired, or already ours.
        Returns the fencing token on success, None otherwise."""
        now = time.time() if now is None else now
        try:
            with self._claim(partition):
                cur = self._read(partition)
                if cur is not None:
                    if cur.get("owner") == self.owner:
                        return int(cur.get("fence", 0))
                    if float(cur.get("expires", 0)) > now:
                        return None  # live foreign lease
                fence = self._next_fence(partition, cur)
                self._write(partition, {
                    "owner": self.owner, "expires": now + self.ttl_s,
                    "fence": fence,
                })
                return fence
        except _ClaimBusy:
            return None  # a peer is arbitrating; try again next sweep

    def renew(self, partition: str,
              now: Optional[float] = None) -> bool:
        now = time.time() if now is None else now
        try:
            with self._claim(partition):
                cur = self._read(partition)
                if cur is None or cur.get("owner") != self.owner:
                    return False  # deposed
                self._write(partition, {**cur, "expires": now + self.ttl_s})
                return True
        except _ClaimBusy:
            # Can't prove ownership right now; claiming failure is the
            # safe answer (the worker stands down, fencing protects
            # anything it had in flight).
            return False

    def release(self, partition: str) -> None:
        try:
            with self._claim(partition):
                cur = self._read(partition)
                if cur is not None and cur.get("owner") == self.owner:
                    self._write(partition, {**cur, "expires": 0})
        except _ClaimBusy:
            pass  # lease will expire on its own

    def owner_of(self, partition: str,
                 now: Optional[float] = None) -> Optional[str]:
        info = self.lease_info(partition, now)
        return info["owner"] if info is not None else None

    def lease_info(self, partition: str,
                   now: Optional[float] = None) -> Optional[dict]:
        """The live lease as ``{"owner", "fence", "expires"}`` (None if
        unowned/expired). The fence is what lets a READER tell a stale
        pre-takeover (or pre-split) owner from the live one — owner
        strings alone cannot, since a restarted worker reuses its
        slot name while the fence strictly advances."""
        now = time.time() if now is None else now
        cur = self._read(partition)
        if cur is None or float(cur.get("expires", 0)) <= now:
            return None
        return {"owner": cur.get("owner"),
                "fence": int(cur.get("fence", 0)),
                "expires": float(cur.get("expires", 0))}


# The envelope's head as `FencedCheckpointStore.save` writes it
# (`json.dumps` of {"fence", "owner", "state"}, in that order): the
# fence and owner lie within the first few bytes of the file.
_HEAD_BYTES = 4096
_HEAD_FENCE = '{"fence": '
_HEAD_OWNER = ', "owner": '
_HEAD_STATE = ', "state": '
_HEAD_DECODER = json.JSONDecoder()


def _envelope_head(text: str) -> Optional[Tuple[int, Optional[str]]]:
    """`(fence, owner)` from an envelope's head, or None unless `text`
    opens with exactly ``{"fence": <int>, "owner": <string or null>,
    "state": `` — the form `save` writes. Each value must be followed
    by the next literal, so a head cut short never parses."""
    if not text.startswith(_HEAD_FENCE):
        return None
    try:
        fence, i = _HEAD_DECODER.raw_decode(text, len(_HEAD_FENCE))
        if type(fence) is not int or not text.startswith(_HEAD_OWNER, i):
            return None
        owner, i = _HEAD_DECODER.raw_decode(text, i + len(_HEAD_OWNER))
    except ValueError:
        return None
    if owner is not None and type(owner) is not str:
        return None
    return (fence, owner) if text.startswith(_HEAD_STATE, i) else None


class EncodedJson:
    """A value already encoded as JSON: `parts`, ASCII bytes that
    joined are exactly ``json.dumps(value)`` (default separators,
    ``ensure_ascii``). `FencedCheckpointStore.save` writes the parts in
    the value's place, wherever it sits in the state, without joining
    them into one string."""

    __slots__ = ("parts",)

    def __init__(self, parts: List[bytes]):
        self.parts = parts


# The stand-in `save` encodes in place of each `EncodedJson` before
# splicing its parts in; the random suffix keeps it out of real data.
_SPLICE_MARK = "__encoded_json_" + os.urandom(12).hex()
_SPLICE_TEXT = json.dumps(_SPLICE_MARK)
# The parts run from a few hundred bytes to a few KiB: write them to
# the file a MiB at a time.
_WRITE_BUFFER = 1 << 20


def _encode_envelope(envelope: dict) -> List[bytes]:
    """``json.dumps(envelope)`` as byte chunks: one, unless the envelope
    holds `EncodedJson` values, whose parts go in as they are between
    the chunks of the text `json.dumps` writes around them."""
    spliced: List[EncodedJson] = []

    def mark(o):
        if isinstance(o, EncodedJson):
            spliced.append(o)
            return _SPLICE_MARK
        raise TypeError(
            f"Object of type {type(o).__name__} is not JSON serializable")

    text = json.dumps(envelope, default=mark)
    if not spliced:
        return [text.encode()]
    pieces = text.split(_SPLICE_TEXT)
    if len(pieces) != len(spliced) + 1:
        raise ValueError("checkpoint state holds the splice marker")
    chunks = [pieces[0].encode()]
    for enc, piece in zip(spliced, pieces[1:]):
        chunks.extend(enc.parts)
        chunks.append(piece.encode())
    return chunks


class FencedCheckpointStore:
    """Durable lambda checkpoints whose writes REJECT deposed owners.

    The reference's deli checkpoints to Mongo with the partition
    epoch as the fencing token; here each key is a JSON file
    ``{"fence", "owner", "state"}``. `save` encodes the envelope, then
    flushes it in a read-gate-write critical section under an OS file
    lock; a role that writes off its pump thread calls the two apart
    (`encode` on the pump, `flush` on its writer). A writer carrying a
    fence lower than the stored one — or an equal fence under a
    different owner than the one that first bound it — gets
    `FencedError`, so a deposed lease holder can never roll a
    successor's checkpoint back (the exactly-once recovery contract).

    The gate reads only the stored envelope's head (`_HEAD_BYTES`),
    where `save` puts the fence and owner ahead of the state; an
    envelope of any other form (key order, an owner longer than the
    head, an empty or unreadable file) falls back to a full `load`,
    counted by `checkpoint_fence_full_loads_total{role}`. A valid head
    decides the check even if the body is damaged: that can only be
    stricter than loading it, since a successor always binds a higher
    fence.
    """

    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self._instruments: Dict[str, tuple] = {}

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.ckpt.json")

    def _instruments_of(self, key: str) -> tuple:
        """The save's phase spans (fence, encode, write) and its
        full-load fallback counter for `key`, built on its first
        save."""
        inst = self._instruments.get(key)
        if inst is None:
            inst = self._instruments[key] = tuple(
                span(f"checkpoint.{phase}", role=key)
                for phase in ("fence", "encode", "write")) + (
                get_registry().counter(
                    "checkpoint_fence_full_loads_total", role=key),)
        return inst

    def _bound_fence(self, key: str,
                     full_loads) -> Optional[Tuple[int, Optional[str]]]:
        """The stored envelope's `(fence, owner)`, or None where there
        is no readable envelope (no check). Reads the head; an envelope
        of another form is loaded whole and counted in `full_loads`."""
        try:
            with open(self._path(key)) as f:
                head = _envelope_head(f.read(_HEAD_BYTES))
        except FileNotFoundError:
            return None
        except (OSError, ValueError):
            head = None
        if head is not None:
            return head
        full_loads.inc()
        cur = self.load(key)
        return None if cur is None else (int(cur.get("fence", 0)),
                                         cur.get("owner"))

    def load(self, key: str) -> Optional[dict]:
        """The checkpoint envelope ({"fence", "owner", "state"}) or
        None."""
        try:
            with open(self._path(key)) as f:
                d = json.load(f)
            return d if isinstance(d, dict) and "state" in d else None
        except (OSError, ValueError):
            return None

    def encode(self, key: str, state: Any, fence: int,
               owner: Optional[str] = None) -> List[bytes]:
        """The envelope ``{"fence", "owner", "state"}`` as the byte
        chunks `flush` writes: ``json.dumps`` of it, an `EncodedJson`
        value anywhere in `state` as its parts and the text around it
        chunk by chunk (the `checkpoint.encode` phase then encodes only
        that text), so a role that keeps its state pre-encoded writes
        the same bytes without one envelope-sized string. The chunks
        are bytes: nothing done to `state` afterwards reaches them."""
        _, sp_encode, _, _ = self._instruments_of(key)
        with sp_encode:
            return _encode_envelope(
                {"fence": fence, "owner": owner, "state": state})

    def flush(self, key: str, payload: List[bytes], fence: int,
              owner: Optional[str] = None,
              lock_timeout_s: Optional[float] = None) -> int:
        """Fenced write of `payload`, an envelope that `encode` made
        with the same `fence` and `owner`; returns its size in bytes
        (the checkpoint-bytes metric's source). Lock, fence check, then
        the chunks written, fsynced and moved into place, so a reader
        finds the previous envelope or this one, whole."""
        import fcntl

        sp_fence, _, sp_write, full_loads = self._instruments_of(key)
        lock_path = self._path(key) + ".lock"
        with open(lock_path, "a+") as lk, contextlib.ExitStack() as gate:
            # The fence phase: lock wait, the stored envelope's head
            # (its full load only where the head is of another form,
            # and freeing it), fence check.
            gate.enter_context(sp_fence)
            if lock_timeout_s is None:
                fcntl.flock(lk.fileno(), fcntl.LOCK_EX)
            else:
                deadline = time.time() + lock_timeout_s
                while True:
                    try:
                        fcntl.flock(
                            lk.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB
                        )
                        break
                    except OSError:
                        if time.time() > deadline:
                            raise TimeoutError(
                                f"checkpoint lock {key!r} held past "
                                f"{lock_timeout_s}s"
                            )
                        time.sleep(0.005)
            try:
                bound = self._bound_fence(key, full_loads)
                if bound is not None:
                    _check_fence(*bound, fence, owner,
                                 f"checkpoint {key!r}")
                gate.close()
                tmp = self._path(key) + f".tmp.{os.getpid()}"
                with sp_write:
                    check_disk_fault("checkpoint")
                    with open(tmp, "wb", buffering=_WRITE_BUFFER) as f:
                        f.writelines(payload)
                        f.flush()
                        fsync_file(f, "checkpoint")
                    os.replace(tmp, self._path(key))
            finally:
                fcntl.flock(lk.fileno(), fcntl.LOCK_UN)
        return sum(map(len, payload))

    def save(self, key: str, state: Any, fence: int,
             owner: Optional[str] = None,
             lock_timeout_s: Optional[float] = None) -> int:
        """Fenced write of `state`: `encode`, then `flush`. The file
        holds ``json.dumps({"fence", "owner", "state"})``; returns its
        size in bytes."""
        return self.flush(key, self.encode(key, state, fence, owner),
                          fence, owner, lock_timeout_s)


HASH_SPACE = 1 << 32  # the document hash ring [0, 2^32)


def doc_hash(doc_id: str) -> int:
    """A document's stable position on the hash ring — the single
    hashing rule both placement schemes derive from (modulo-N
    `partition_of`, and the elastic hash-range leases)."""
    import hashlib

    h = hashlib.sha256(doc_id.encode()).digest()
    return int.from_bytes(h[:4], "big")


def partition_of(doc_id: str, n_partitions: int) -> int:
    """Stable document-space partitioning (the Kafka partition-by-doc
    role, lambdas-driver/src/document-router)."""
    return doc_hash(doc_id) % n_partitions


def range_id(lo: int, hi: int, epoch: Optional[int] = None) -> str:
    """THE range naming rule: the half-open hash range ``[lo, hi)``
    born at `epoch` is ``r{lo:08x}-{hi:08x}[-e{epoch}]`` — lease keys,
    topic names and topology entries all derive from this one function
    (the elastic twin of `partition_suffix`), so a range's identities
    can never drift. The epoch tag (absent only for the bootstrap
    topology) makes every INCARNATION of a range a fresh identity: a
    merge that recreates an ancestor's exact bounds must NOT inherit
    the ancestor's topics or checkpoint key — its state comes from its
    immediate predecessors, not from a dead ancestor's stale
    checkpoint."""
    base = f"r{lo:08x}-{hi:08x}"
    return base if epoch is None else f"{base}-e{int(epoch)}"


class RangeLeaseStore:
    """Hash-range (virtual-partition) leases + the fenced topology
    epoch record — the coordination substrate of the ELASTIC fabric.

    Two pieces, both arbitrated by the same ``O_CREAT|O_EXCL`` claim
    machinery as the classic partition leases:

    - **Range leases** — a `LeaseManager` whose keys are range lease
      names (``deli-r{lo:08x}-{hi:08x}``) and whose fencing tokens
      come from ONE fabric-wide monotonic counter (`fence_scope`), so
      a successor's fence is comparable on any predecessor's topics —
      the property a split/merge handoff rests on.
    - **Topology epochs** — ``<shared>/topology.json`` maps the live
      ranges to their topic names. Commits are fenced like checkpoints:
      a writer proposes against the epoch it READ, under the claim, and
      a concurrent commit wins the CAS — the loser re-reads and
      retries or stands down. Epochs only ever advance; every range id
      ever live stays in ``history`` so records written under epoch E
      remain readable (merged catch-up) after E+1.

    The topology shape (pure JSON, operator-readable):

    ``{"epoch": E, "ranges": [{"lo", "hi", "rid", "raw", "deltas",
    "preds": [rid, ...]}, ...], "history": [rid, ...]}``

    ``preds`` names the range(s) an entry replaced (one parent for a
    split child, two parents for a merge survivor): successors restore
    the predecessors' final fenced checkpoints sliced to their range
    and close the durable gap with the exactly-once ``inOff`` scan.
    """

    TOPOLOGY_CLAIM = "__topology__"

    def __init__(self, shared_dir: str, owner: str, ttl_s: float = 1.0,
                 claim_ttl_s: Optional[float] = None):
        self.shared_dir = shared_dir
        self.leases = LeaseManager(
            os.path.join(shared_dir, "leases"), owner, ttl_s,
            claim_ttl_s=claim_ttl_s
            if claim_ttl_s is not None else max(0.25, ttl_s / 2),
            fence_scope="__fabric__",
        )
        self.topology_path = os.path.join(shared_dir, "topology.json")

    # -------------------------------------------------------- topology

    def read_topology(self) -> Optional[dict]:
        try:
            with open(self.topology_path) as f:
                d = json.load(f)
        except (OSError, ValueError):
            return None
        if (isinstance(d, dict) and isinstance(d.get("ranges"), list)
                and isinstance(d.get("epoch"), int)):
            return d
        return None

    def _write_topology(self, topo: dict) -> None:
        tmp = self.topology_path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(topo, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.topology_path)

    def ensure_topology(self, n_ranges: int) -> dict:
        """Bootstrap epoch 1 with `n_ranges` equal hash slices (claim-
        arbitrated, idempotent — whoever loses the race adopts the
        winner's record)."""
        topo = self.read_topology()
        if topo is not None:
            return topo
        try:
            with self.leases._claim(self.TOPOLOGY_CLAIM):
                topo = self.read_topology()
                if topo is None:
                    topo = initial_topology(n_ranges)
                    self._write_topology(topo)
                return topo
        except _ClaimBusy:
            # A peer is bootstrapping right now; wait it out.
            deadline = time.time() + 10 * self.leases.ttl_s
            while time.time() < deadline:
                topo = self.read_topology()
                if topo is not None:
                    return topo
                time.sleep(0.01)
            raise RuntimeError("topology bootstrap claim never resolved")

    def commit_topology(self, topo: dict, expect_epoch: int) -> bool:
        """Fenced CAS: commit `topo` as epoch ``expect_epoch + 1`` iff
        the record still reads `expect_epoch`. Returns False on a lost
        race (the caller re-reads and reconsiders — a topology change
        is an ownership change, and two may not interleave)."""
        try:
            with self.leases._claim(self.TOPOLOGY_CLAIM):
                cur = self.read_topology()
                if cur is not None and cur["epoch"] != expect_epoch:
                    return False
                self._write_topology({**topo, "epoch": expect_epoch + 1})
                return True
        except _ClaimBusy:
            return False


def _range_entry(lo: int, hi: int, preds: Tuple[str, ...] = (),
                 epoch: Optional[int] = None) -> dict:
    """One topology entry: the range, its id, and the topic names it
    maps to (the epoch record IS the ranges→topics map)."""
    rid = range_id(lo, hi, epoch)
    return {"lo": int(lo), "hi": int(hi), "rid": rid,
            "raw": f"rawdeltas-{rid}", "deltas": f"deltas-{rid}",
            "preds": list(preds)}


def initial_topology(n_ranges: int) -> dict:
    """Epoch-1 topology: `n_ranges` equal slices of the hash ring."""
    n = int(n_ranges)
    if n < 1:
        raise ValueError(f"n_ranges must be >= 1: {n_ranges}")
    bounds = [HASH_SPACE * i // n for i in range(n)] + [HASH_SPACE]
    ranges = [_range_entry(bounds[i], bounds[i + 1]) for i in range(n)]
    return {"epoch": 1, "ranges": ranges,
            "history": [e["rid"] for e in ranges]}


def split_ranges(topo: dict, rid: str, at: Optional[int] = None) -> dict:
    """`topo` with range `rid` split into two children at hash `at`
    (default: the midpoint). Pure function — the caller commits the
    result through `RangeLeaseStore.commit_topology` (which bumps the
    epoch) AFTER writing the parent's final fenced checkpoint."""
    entry = next((e for e in topo["ranges"] if e["rid"] == rid), None)
    if entry is None:
        raise ValueError(f"range {rid!r} not in topology")
    lo, hi = entry["lo"], entry["hi"]
    at = (lo + hi) // 2 if at is None else int(at)
    if not lo < at < hi:
        raise ValueError(f"split point {at} outside ({lo}, {hi})")
    # Children are tagged with the epoch the commit will install
    # (commit CAS is against topo["epoch"], so the successor epoch is
    # known here): a fresh incarnation never collides with an
    # ancestor's topics or checkpoint key.
    born = topo["epoch"] + 1
    children = [_range_entry(lo, at, preds=(rid,), epoch=born),
                _range_entry(at, hi, preds=(rid,), epoch=born)]
    ranges = sorted(
        [e for e in topo["ranges"] if e["rid"] != rid] + children,
        key=lambda e: e["lo"],
    )
    history = list(topo.get("history", []))
    history += [c["rid"] for c in children if c["rid"] not in history]
    return {"epoch": topo["epoch"], "ranges": ranges, "history": history}


def merge_ranges(topo: dict, rid_a: str, rid_b: str) -> dict:
    """`topo` with ADJACENT ranges `rid_a`/`rid_b` merged into one
    (order-insensitive). The survivor's `preds` names both parents —
    its successor restores both final checkpoints and closes both
    durable gaps."""
    a = next((e for e in topo["ranges"] if e["rid"] == rid_a), None)
    b = next((e for e in topo["ranges"] if e["rid"] == rid_b), None)
    if a is None or b is None:
        raise ValueError(f"range {rid_a!r}/{rid_b!r} not in topology")
    if a["lo"] > b["lo"]:
        a, b = b, a
    if a["hi"] != b["lo"]:
        raise ValueError(
            f"ranges {a['rid']}/{b['rid']} are not adjacent"
        )
    merged = _range_entry(a["lo"], b["hi"],
                          preds=(a["rid"], b["rid"]),
                          epoch=topo["epoch"] + 1)
    ranges = sorted(
        [e for e in topo["ranges"]
         if e["rid"] not in (a["rid"], b["rid"])] + [merged],
        key=lambda e: e["lo"],
    )
    history = list(topo.get("history", []))
    if merged["rid"] not in history:
        history.append(merged["rid"])
    return {"epoch": topo["epoch"], "ranges": ranges, "history": history}


def range_containing(topo: dict, h: int) -> dict:
    """The topology entry whose ``[lo, hi)`` contains hash `h` (the
    ranges are contiguous and sorted, so this cannot miss)."""
    import bisect

    ranges = topo["ranges"]
    i = bisect.bisect_right([e["lo"] for e in ranges], h) - 1
    return ranges[max(0, i)]


def range_for_doc(topo: dict, doc_id: str) -> dict:
    """`(epoch, hash(doc))` routing: the live range `doc_id` maps to —
    the elastic replacement for ``doc % N``."""
    return range_containing(topo, doc_hash(doc_id))


def partition_suffix(name: str, partition: int) -> str:
    """THE partition naming rule: `name` sliced to partition `k` is
    ``{name}-p{k}`` — topics (``rawdeltas-p3`` → ``deltas-p3``), lease
    keys, checkpoint keys and role names all derive from this one
    function, so the fabric's identities can never drift apart."""
    return f"{name}-p{int(partition)}"


def record_partition(rec: Any, n_partitions: int) -> int:
    """The partition one INGRESS record routes to: by its doc id (a
    boxcar carries exactly one doc, so it rides whole). Doc-less junk
    pins to partition 0 — any single consistent home keeps offsets
    deterministic."""
    if n_partitions <= 1:
        return 0
    doc = rec.get("doc") if isinstance(rec, dict) else None
    return partition_of(doc, n_partitions) if isinstance(doc, str) else 0


def split_by_partition(records: List[Any],
                       n_partitions: int) -> Dict[int, List[Any]]:
    """Ingress records grouped by `record_partition`, input order
    preserved within each group — the one grouping rule every router
    edge (`shard_fabric.ShardRouter`, `LocalServer._route_raw`) shares,
    so a record can never route differently on different edges."""
    out: Dict[int, List[Any]] = {}
    for rec in records:
        out.setdefault(record_partition(rec, n_partitions), []).append(rec)
    return out


def lease_table(directory: str,
                now: Optional[float] = None) -> Dict[str, dict]:
    """Live leases in `directory` as ``{partition_name: {"owner",
    "fence", "expires"}}`` — the operator's (and chaos harness's) view
    of who owns what right now, WITH the fencing token: an owner
    string alone cannot distinguish a stale pre-split/pre-takeover
    holder from the live one, the fence can (it strictly advances on
    every ownership change). Read-only: no claim taken, so the
    snapshot may be an instant stale, which is all a monitoring
    surface needs. Liveness semantics are `LeaseManager.lease_info`'s
    — one place owns the expiry rule."""
    out: Dict[str, dict] = {}
    if not os.path.isdir(directory):
        return out
    probe = LeaseManager(directory, owner="__observer__")
    now = time.time() if now is None else now
    for fn in os.listdir(directory):
        if not fn.endswith(".lease"):
            continue
        name = fn[:-len(".lease")]
        info = probe.lease_info(name, now)
        if info is not None:
            out[name] = info
    return out


def lease_owners(directory: str,
                 now: Optional[float] = None) -> Dict[str, str]:
    """`lease_table` collapsed to {partition_name: owner} — the
    historical shape, still what most health surfaces render."""
    return {k: v["owner"] for k, v in lease_table(directory, now).items()}
