"""Kafka connectors: external ingestion/egress with replayable offsets.

Parity: ``wf/kafka/kafka_source.hpp:127-519`` (consumer-group replicas, a
poll loop with idle timeout, a user deserialization functor returning a
continue flag, explicit start offsets) and ``wf/kafka/kafka_sink.hpp:71-379``
(user serializer returning (topic, partition, payload)).

The reference links librdkafka; here the transport is pluggable behind one
small interface (subscribe/consume/produce/flush/close):

- broker string ``"memory://<name>"`` uses the built-in in-process
  ``MemoryBroker`` (partitioned topics, offsets, consumer groups) — it
  exercises the full replay/offset surface without a server;
- any other broker string goes through ``ConfluentTransport``
  (confluent_kafka / librdkafka, preferred) or ``KafkaPythonTransport``
  (kafka-python). A missing client library fails fast at operator
  CONSTRUCTION with a clear error, never silently at runtime; the
  adapters are unit-tested against injected fake client modules.
"""

from __future__ import annotations

import os
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..basic import OpType, RoutingMode, WindFlowError, current_time_usecs
from ..operators.base import BasicOperator, BasicReplica, arity
from ..operators.source import SourceShipper
from ..sinks.transactional import FencedWriteError


# ---------------------------------------------------------------------------
# transient-error retry (jittered exponential backoff): a broker hiccup
# must not surface as a worker crash — bounded attempts with backoff, a
# Kafka_reconnects stat per retry, THEN the error propagates to the
# supervisor/wait_end like any other failure
# ---------------------------------------------------------------------------
def _kafka_retry_attempts() -> int:
    try:
        return max(0, int(os.environ.get("WF_KAFKA_RETRIES", "5")))
    except ValueError:
        return 5  # malformed knob must not take down the graph


def _kafka_retry_base_s() -> float:
    try:
        return max(0.0,
                   float(os.environ.get("WF_KAFKA_RETRY_BASE_MS", "100"))
                   / 1e3)
    except ValueError:
        return 0.1


def _retrying(transport, fn: Callable, what: str):
    """Run ``fn`` with bounded retry on the transport's transient error
    classes: the k-th retry sleeps ``base * 2**k`` seconds with uniform
    jitter in [0.5, 1.0] of that value (a replica fleet must not retry a
    flapping broker in lockstep). Every retry invokes
    ``transport.on_retry`` (the replica counts it as Kafka_reconnects);
    exhausted attempts re-raise the last error."""
    transients = transport._transient_excs()
    if not transients:
        return fn()
    attempts = _kafka_retry_attempts()
    base = _kafka_retry_base_s()
    for attempt in range(attempts + 1):
        try:
            return fn()
        except transients as e:
            # confluent wraps a KafkaError carrying .fatal() in args[0]:
            # authentication/config errors never heal by retry
            inner = e.args[0] if getattr(e, "args", None) else None
            fatal = getattr(inner, "fatal", None)
            if callable(fatal) and fatal():
                raise
            if attempt >= attempts:
                raise WindFlowError(
                    f"Kafka {what}: still failing after {attempts} "
                    f"retr{'y' if attempts == 1 else 'ies'}: "
                    f"{type(e).__name__}: {e}") from e
            cb = getattr(transport, "on_retry", None)
            if cb is not None:
                cb()
            delay = base * (2 ** attempt)
            time.sleep(delay * (0.5 + 0.5 * random.random()))


class KafkaMessage:
    __slots__ = ("topic", "partition", "offset", "payload", "timestamp")

    def __init__(self, topic, partition, offset, payload, timestamp) -> None:
        self.topic = topic
        self.partition = partition
        self.offset = offset
        self.payload = payload
        self.timestamp = timestamp


# ---------------------------------------------------------------------------
# In-process broker (the test transport)
# ---------------------------------------------------------------------------
class MemoryBroker:
    _registry: Dict[str, "MemoryBroker"] = {}
    _reg_lock = threading.Lock()

    def __init__(self, name: str, n_partitions: int = 4) -> None:
        self.name = name
        self.n_partitions = n_partitions
        self._topics: Dict[str, List[List[KafkaMessage]]] = {}
        self._lock = threading.Lock()
        self._group_assign: Dict[Tuple[str, str], Dict[int, int]] = {}
        # consumer-group committed offsets ((group, topic, partition) ->
        # next offset) — written by MemoryTransport.commit_offsets when a
        # checkpoint finalizes, mirroring a real broker's offset store
        self.committed: Dict[Tuple[str, str, int], int] = {}
        # transactional-producer state (exactly-once sinks): per
        # transactional id a fence generation (zombie producers are
        # refused, Kafka's producer-epoch fencing), prepared-but-
        # uncommitted epoch buffers (durable across a producer's death —
        # the analog of the broker's transaction log), and the committed
        # epoch set (idempotent commit: a replayed epoch is discarded)
        self.txn_fences: Dict[str, int] = {}
        self.txn_prepared: Dict[str, Dict[int, List[Tuple]]] = {}
        self.txn_committed: Dict[str, set] = {}
        self.fenced_attempts = 0

    @classmethod
    def get(cls, name: str, n_partitions: int = 4) -> "MemoryBroker":
        with cls._reg_lock:
            b = cls._registry.get(name)
            if b is None:
                b = cls._registry[name] = MemoryBroker(name, n_partitions)
            return b

    @classmethod
    def reset(cls) -> None:
        with cls._reg_lock:
            cls._registry.clear()

    def _topic(self, topic: str) -> List[List[KafkaMessage]]:
        with self._lock:
            t = self._topics.get(topic)
            if t is None:
                t = self._topics[topic] = [[] for _ in range(self.n_partitions)]
            return t

    def produce(self, topic: str, payload: Any,
                partition: Optional[int] = None, key: Any = None) -> None:
        t = self._topic(topic)
        with self._lock:
            if partition is None:
                partition = (hash(key) % self.n_partitions if key is not None
                             else sum(len(p) for p in t) % self.n_partitions)
            part = t[partition % self.n_partitions]
            part.append(KafkaMessage(topic, partition % self.n_partitions,
                                     len(part), payload,
                                     current_time_usecs()))

    def assign_partitions(self, topic: str, group: str, member: int,
                          n_members: int) -> List[int]:
        """Cooperative assignment: partition p -> member p % n_members
        (the reference relies on Kafka's group rebalance,
        ``kafka_source.hpp:77-115``)."""
        return [p for p in range(self.n_partitions) if p % n_members == member]

    def poll(self, topic: str, partition: int, offset: int
             ) -> Optional[KafkaMessage]:
        t = self._topic(topic)
        with self._lock:
            part = t[partition]
            if offset < len(part):
                return part[offset]
        return None

    def poll_run(self, topic: str, partition: int, offset: int,
                 max_n: int) -> List[KafkaMessage]:
        """Contiguous run from one partition — the batch-poll primitive
        the columnar block adapter rides (one lock round per partition
        instead of one per message)."""
        t = self._topic(topic)
        with self._lock:
            return t[partition][offset:offset + max_n]

    def end_offset(self, topic: str, partition: int) -> int:
        t = self._topic(topic)
        with self._lock:
            return len(t[partition])

    # -- transactions (exactly-once sinks) ---------------------------------
    def txn_init(self, txn_id: str) -> int:
        """(Re)initialize a transactional producer: bump the fence
        generation — every older producer of the same id is now a zombie
        whose writes are refused (``kafka_sink`` EOS parity with Kafka's
        ``initTransactions`` producer-epoch bump)."""
        with self._lock:
            gen = self.txn_fences.get(txn_id, 0) + 1
            self.txn_fences[txn_id] = gen
            self.txn_prepared.setdefault(txn_id, {})
            self.txn_committed.setdefault(txn_id, set())
            return gen

    def _txn_check(self, txn_id: str, gen: int) -> None:
        if self.txn_fences.get(txn_id) != gen:
            self.fenced_attempts += 1
            raise FencedWriteError(
                f"Kafka transactional producer {txn_id!r} generation "
                f"{gen} is fenced (current generation "
                f"{self.txn_fences.get(txn_id)}): a newer replica owns "
                "this transaction log")

    def txn_check(self, txn_id: str, gen: int) -> None:
        with self._lock:
            self._txn_check(txn_id, gen)

    def txn_prepare(self, txn_id: str, gen: int, epoch: int,
                    records: List[Tuple]) -> None:
        """Phase 1: the epoch's records become durable in the broker's
        transaction log, invisible to consumers until commit."""
        with self._lock:
            self._txn_check(txn_id, gen)
            self.txn_prepared[txn_id][epoch] = list(records)

    def txn_is_committed(self, txn_id: str, epoch: int) -> bool:
        with self._lock:
            return epoch in self.txn_committed.get(txn_id, ())

    def txn_commit(self, txn_id: str, gen: int, epoch: int) -> bool:
        """Phase 2: append the prepared records to their topics. False
        when the epoch was already committed (idempotent — the replayed
        duplicate is discarded)."""
        with self._lock:
            self._txn_check(txn_id, gen)
            if epoch in self.txn_committed[txn_id]:
                self.txn_prepared[txn_id].pop(epoch, None)
                return False
            records = self.txn_prepared[txn_id].pop(epoch, [])
            self.txn_committed[txn_id].add(epoch)
        for topic, partition, key, payload in records:
            self.produce(topic, payload, partition, key)
        return True

    def txn_abort(self, txn_id: str, gen: int, epoch: int) -> bool:
        with self._lock:
            self._txn_check(txn_id, gen)
            return self.txn_prepared[txn_id].pop(epoch, None) is not None

    def txn_prepared_epochs(self, txn_id: str) -> List[int]:
        with self._lock:
            return sorted(self.txn_prepared.get(txn_id, {}))


def _parse_brokers(brokers: str):
    if brokers.startswith("memory://"):
        return ("memory", brokers[len("memory://"):])
    return ("kafka", brokers)


def _require_kafka_client():
    try:
        import confluent_kafka  # noqa: F401
        return "confluent"
    except ImportError:
        pass
    try:
        import kafka  # noqa: F401
        return "kafka-python"
    except ImportError:
        raise WindFlowError(
            "Kafka connector: no Kafka client library available "
            "(confluent_kafka / kafka-python); use a memory:// broker or "
            "install a client") from None


# ---------------------------------------------------------------------------
# Transports: the replica loops speak this small interface; memory:// is
# the in-process test transport, real brokers go through confluent_kafka
# or kafka-python (the reference links librdkafka directly,
# ``kafka_source.hpp:127-519`` / ``kafka_sink.hpp:71-379``)
# ---------------------------------------------------------------------------
class MemoryTransport:
    supports_transactions = True

    def __init__(self, name: str) -> None:
        self.broker = MemoryBroker.get(name)
        self._parts: List[Tuple[str, int]] = []
        self._pos: Dict[Tuple[str, int], int] = {}
        self._rr = 0
        self._group = "windflow"
        self.on_retry = None  # in-process broker: no transient failures

    def _transient_excs(self) -> tuple:
        return ()

    def subscribe(self, topics, group, member, n_members, offsets) -> bool:
        self._group = group
        if offsets:
            # explicit offsets = explicit assignment of ONLY the listed
            # partitions (identical semantics to the real transports)
            for (t, p), o in _member_share(offsets, member,
                                           n_members).items():
                self._parts.append((t, p))
                self._pos[(t, p)] = o
        else:
            for t in topics:
                for p in self.broker.assign_partitions(t, group, member,
                                                       n_members):
                    self._parts.append((t, p))
                    self._pos[(t, p)] = 0
        return bool(self._parts)

    def consume(self) -> Optional[KafkaMessage]:
        for _ in range(len(self._parts)):
            tp = self._parts[self._rr]
            self._rr = (self._rr + 1) % len(self._parts)
            msg = self.broker.poll(tp[0], tp[1], self._pos[tp])
            if msg is not None:
                self._pos[tp] += 1
                return msg
        return None

    def consume_batch(self, max_n: int) -> List[KafkaMessage]:
        """Batch poll for the columnar block adapter: up to ``max_n``
        messages as contiguous per-partition runs (round-robin across
        assigned partitions), advancing the same per-partition cursors
        ``snapshot_positions`` records — offset semantics are identical
        to the per-message path."""
        out: List[KafkaMessage] = []
        for _ in range(len(self._parts)):
            if len(out) >= max_n:
                break
            tp = self._parts[self._rr]
            self._rr = (self._rr + 1) % len(self._parts)
            run = self.broker.poll_run(tp[0], tp[1], self._pos[tp],
                                       max_n - len(out))
            if run:
                self._pos[tp] += len(run)
                out.extend(run)
        return out

    def produce(self, topic, payload, partition=None, key=None) -> None:
        self.broker.produce(topic, payload, partition, key)

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass

    # -- checkpointing -----------------------------------------------------
    def snapshot_positions(self) -> Dict[Tuple[str, int], int]:
        """Next-to-consume offset per assigned partition (the replayable
        cursor a checkpoint records)."""
        return dict(self._pos)

    def commit_offsets(self, offsets: Dict[Tuple[str, int], int]) -> None:
        """Group-offset commit on checkpoint finalize (at-least-once: a
        restart WITHOUT a checkpoint resumes from these)."""
        with self.broker._lock:
            for (t, p), o in offsets.items():
                self.broker.committed[(self._group, t, p)] = o


def _member_share(offsets, member: int, n_members: int):
    """Deterministic split of explicitly-assigned partitions across the
    replica group (partition p -> member p % n_members, the same rule
    MemoryBroker.assign_partitions uses). ALL transports treat a
    non-empty offsets map as an explicit assignment: only the listed
    partitions are consumed, from the given positions — so memory:// and
    real brokers behave identically."""
    return {(t, p): o for (t, p), o in offsets.items()
            if p % n_members == member}


class ConfluentTransport:
    """confluent_kafka (librdkafka) adapter. ``module`` is injectable for
    tests (a fake with Consumer/Producer/TopicPartition)."""

    supports_transactions = True  # librdkafka transactional producer

    def __init__(self, brokers: str, module=None) -> None:
        if module is None:
            import confluent_kafka as module  # noqa: PLC0415
        self._ck = module
        self.brokers = brokers
        self._consumer = None
        self._producer = None
        self._txn_producer_obj = None
        self._delivery_errors = 0
        # checkpointing turns auto-commit OFF: offsets commit only when
        # the coordinator finalizes a checkpoint (at-least-once end to
        # end); KafkaSourceReplica flips this before subscribe
        self.auto_commit = True
        # transient-error retry: the owning replica wires this to its
        # Kafka_reconnects counter
        self.on_retry = None

    def _transient_excs(self) -> tuple:
        exc = getattr(self._ck, "KafkaException", None)
        return (exc,) if isinstance(exc, type) else ()

    def subscribe(self, topics, group, member, n_members, offsets) -> bool:
        ck = self._ck

        def _connect():
            return ck.Consumer({
                "bootstrap.servers": self.brokers,
                "group.id": group,
                "enable.auto.commit": self.auto_commit,
                "auto.offset.reset": "earliest",
            })

        self._consumer = _retrying(self, _connect, "consumer connect")
        if offsets:
            # explicit offsets = explicit assignment (reference
            # kafka_source.hpp manual-offset mode): the listed partitions
            # are split across the replica group deterministically so
            # parallel replicas never double-consume
            mine = _member_share(offsets, member, n_members)
            if not mine:
                return False
            self._consumer.assign([ck.TopicPartition(t, p, o)
                                   for (t, p), o in mine.items()])
        else:
            self._consumer.subscribe(list(topics))
        return True

    def consume(self) -> Optional[KafkaMessage]:
        msg = _retrying(self, lambda: self._consumer.poll(0.01), "consume")
        if msg is None:
            return None
        err = msg.error()
        if err is not None:
            if getattr(err, "fatal", lambda: False)():
                raise WindFlowError(f"Kafka consumer error: {err}")
            return None  # transient (e.g. partition EOF)
        ts = msg.timestamp()
        ts_us = ts[1] * 1000 if ts and ts[1] > 0 else current_time_usecs()
        return KafkaMessage(msg.topic(), msg.partition(), msg.offset(),
                            msg.value(), ts_us)

    def consume_batch(self, max_n: int) -> List[KafkaMessage]:
        """librdkafka batch poll (``Consumer.consume``); falls back to
        repeated single polls when the client (or an injected fake)
        lacks it. Transient per-message errors are skipped, fatal ones
        raise — same policy as ``consume``."""
        batch_fn = getattr(self._consumer, "consume", None)
        if batch_fn is None:
            out = []
            while len(out) < max_n:
                m = self.consume()
                if m is None:
                    break
                out.append(m)
            return out
        msgs = _retrying(self, lambda: batch_fn(max_n, 0.01), "consume")
        out = []
        for msg in msgs or ():
            err = msg.error()
            if err is not None:
                if getattr(err, "fatal", lambda: False)():
                    raise WindFlowError(f"Kafka consumer error: {err}")
                continue
            ts = msg.timestamp()
            ts_us = (ts[1] * 1000 if ts and ts[1] > 0
                     else current_time_usecs())
            out.append(KafkaMessage(msg.topic(), msg.partition(),
                                    msg.offset(), msg.value(), ts_us))
        return out

    def _ensure_producer(self):
        if self._producer is None:
            self._producer = self._ck.Producer(
                {"bootstrap.servers": self.brokers})
            self._delivery_errors = 0

        return self._producer

    def _on_delivery(self, err, msg) -> None:
        if err is not None:
            self._delivery_errors += 1

    def produce(self, topic, payload, partition=None, key=None) -> None:
        kwargs = {"on_delivery": self._on_delivery}
        if partition is not None:
            kwargs["partition"] = partition
        if key is not None:
            kwargs["key"] = key
        p = self._ensure_producer()

        def _produce_once():
            p.produce(topic, value=payload, **kwargs)

        for attempt in range(60):
            try:
                _retrying(self, _produce_once, "produce")
                break
            except BufferError:
                # local librdkafka queue full: backpressure, don't crash
                p.poll(1.0)
        else:
            raise WindFlowError(
                "Kafka sink: local producer queue stayed full for 60s")
        p.poll(0)  # serve delivery callbacks

    def flush(self) -> None:
        if self._producer is None:
            return
        remaining = self._producer.flush(10)
        if remaining or self._delivery_errors:
            raise WindFlowError(
                f"Kafka sink lost data: {self._delivery_errors} delivery "
                f"error(s), {remaining or 0} message(s) still queued at "
                "flush timeout")

    def close(self) -> None:
        if self._consumer is not None:
            self._consumer.close()

    # -- transactions (exactly-once sinks) ---------------------------------
    def txn_produce_epoch(self, txn_id: str, records) -> None:
        """Produce one finalized epoch atomically inside a Kafka
        transaction: consumers in ``read_committed`` see the whole epoch
        or none of it. The transactional id is stable per sink replica,
        so a zombie pre-rebuild producer is fenced by the broker itself
        (``init_transactions`` bumps the producer epoch)."""
        ck = self._ck
        if self._txn_producer_obj is None:
            p = ck.Producer({"bootstrap.servers": self.brokers,
                             "transactional.id": txn_id,
                             "enable.idempotence": True})
            p.init_transactions(30.0)
            self._txn_producer_obj = p
        p = self._txn_producer_obj
        p.begin_transaction()
        try:
            for topic, partition, key, payload in records:
                kwargs = {"on_delivery": self._on_delivery}
                if partition is not None:
                    kwargs["partition"] = partition
                if key is not None:
                    kwargs["key"] = key
                p.produce(topic, value=payload, **kwargs)
            remaining = p.flush(10)
            if remaining or self._delivery_errors:
                raise WindFlowError(
                    f"Kafka exactly-once sink: {self._delivery_errors} "
                    f"delivery error(s), {remaining or 0} message(s) "
                    "unflushed inside the epoch transaction")
            p.commit_transaction(30.0)
        except Exception:
            try:
                p.abort_transaction(10.0)
            except Exception:
                pass  # surfacing the original failure matters more
            raise

    # -- checkpointing -----------------------------------------------------
    def snapshot_positions(self) -> Dict[Tuple[str, int], int]:
        if self._consumer is None:
            return {}
        try:
            tps = self._consumer.assignment()
            return {(tp.topic, tp.partition): tp.offset
                    for tp in self._consumer.position(tps)
                    if tp.offset >= 0}
        except Exception:
            return {}

    def commit_offsets(self, offsets: Dict[Tuple[str, int], int]) -> None:
        if self._consumer is None or not offsets:
            return
        ck = self._ck
        try:
            self._consumer.commit(
                offsets=[ck.TopicPartition(t, p, o)
                         for (t, p), o in offsets.items()],
                asynchronous=False)
        except Exception:
            pass  # best effort: a failed commit only widens the replay


class KafkaPythonTransport:
    """kafka-python adapter (pure-python client). ``module`` injectable."""

    supports_transactions = False  # no transactional producer in kafka-python

    def __init__(self, brokers: str, module=None) -> None:
        if module is None:
            import kafka as module  # noqa: PLC0415
        self._kp = module
        self.brokers = brokers.split(",")
        self._consumer = None
        self._producer = None
        self.auto_commit = True  # see ConfluentTransport
        self.on_retry = None

    def _transient_excs(self) -> tuple:
        exc = getattr(getattr(self._kp, "errors", None), "KafkaError", None)
        return (exc,) if isinstance(exc, type) else ()

    def subscribe(self, topics, group, member, n_members, offsets) -> bool:
        kp = self._kp

        def _connect():
            return kp.KafkaConsumer(
                bootstrap_servers=self.brokers, group_id=group,
                enable_auto_commit=self.auto_commit,
                auto_offset_reset="earliest")

        self._consumer = _retrying(self, _connect, "consumer connect")
        if offsets:
            mine = _member_share(offsets, member, n_members)
            if not mine:
                return False
            tps = [kp.TopicPartition(t, p) for (t, p) in mine]
            self._consumer.assign(tps)
            for (t, p), o in mine.items():
                self._consumer.seek(kp.TopicPartition(t, p), o)
        else:
            self._consumer.subscribe(list(topics))
        return True

    def consume(self) -> Optional[KafkaMessage]:
        polled = _retrying(
            self, lambda: self._consumer.poll(timeout_ms=10, max_records=1),
            "consume")
        for _tp, records in polled.items():
            for r in records:
                ts_us = (r.timestamp * 1000 if getattr(r, "timestamp", 0)
                         else current_time_usecs())
                return KafkaMessage(r.topic, r.partition, r.offset,
                                    r.value, ts_us)
        return None

    def consume_batch(self, max_n: int) -> List[KafkaMessage]:
        """kafka-python batch poll: one ``poll(max_records=max_n)``
        flattened across partitions (records within a partition stay in
        offset order)."""
        polled = _retrying(
            self, lambda: self._consumer.poll(timeout_ms=10,
                                              max_records=max_n),
            "consume")
        out = []
        for _tp, records in polled.items():
            for r in records:
                ts_us = (r.timestamp * 1000 if getattr(r, "timestamp", 0)
                         else current_time_usecs())
                out.append(KafkaMessage(r.topic, r.partition, r.offset,
                                        r.value, ts_us))
        return out

    def _ensure_producer(self):
        if self._producer is None:
            self._producer = self._kp.KafkaProducer(
                bootstrap_servers=self.brokers)
        return self._producer

    def produce(self, topic, payload, partition=None, key=None) -> None:
        p = self._ensure_producer()
        _retrying(self, lambda: p.send(topic, value=payload,
                                       partition=partition, key=key),
                  "produce")

    def flush(self) -> None:
        if self._producer is not None:
            self._producer.flush(timeout=10)

    def close(self) -> None:
        if self._consumer is not None:
            self._consumer.close()

    # -- checkpointing -----------------------------------------------------
    def snapshot_positions(self) -> Dict[Tuple[str, int], int]:
        if self._consumer is None:
            return {}
        try:
            return {(tp.topic, tp.partition): self._consumer.position(tp)
                    for tp in self._consumer.assignment()}
        except Exception:
            return {}

    def commit_offsets(self, offsets: Dict[Tuple[str, int], int]) -> None:
        if self._consumer is None or not offsets:
            return
        kp = self._kp
        try:
            self._consumer.commit(
                {kp.TopicPartition(t, p): kp.OffsetAndMetadata(o, None)
                 for (t, p), o in offsets.items()})
        except Exception:
            pass  # best effort: a failed commit only widens the replay


def make_transport(brokers: str):
    """memory:// -> MemoryTransport; anything else -> the first available
    real client (confluent_kafka preferred, then kafka-python)."""
    kind, target = _parse_brokers(brokers)
    if kind == "memory":
        return MemoryTransport(target)
    client = _require_kafka_client()
    if client == "confluent":
        return ConfluentTransport(target)
    return KafkaPythonTransport(target)


# ---------------------------------------------------------------------------
# Kafka_Source
# ---------------------------------------------------------------------------
class Kafka_Source(BasicOperator):
    """Replicas share a consumer group: partitions split across replicas;
    the user deserialization functor receives (Optional[KafkaMessage],
    shipper) and returns False to stop consuming (``kafka_source.hpp``:
    deser functor returns a continue flag; None message = idle timeout).

    Columnar block mode (``with_columnar_blocks`` on the builder): the
    SAME functor slot instead receives a non-empty LIST of KafkaMessages
    per call (one batch poll, up to ``block_size``) and is expected to
    decode them vectorized and call ``shipper.push_columns`` — no
    per-tuple Python on the hot path. ``None`` still signals the idle
    timeout and ``False`` still stops. Offsets snapshot per-partition
    exactly as in per-message mode (the batch poll advances the same
    cursors), and barriers inject only BETWEEN polls, so the checkpoint
    covers exactly the shipped blocks."""

    op_type = OpType.SOURCE

    def __init__(self, deser_func: Callable, brokers: str,
                 topics: List[str], group_id: str = "windflow",
                 offsets: Optional[Dict[Tuple[str, int], int]] = None,
                 idleness_ms: int = 100, name: str = "kafka_source",
                 parallelism: int = 1, output_batch_size: int = 0) -> None:
        super().__init__(name, parallelism, RoutingMode.NONE,
                         output_batch_size=output_batch_size)
        self.deser_func = deser_func
        self.brokers = brokers
        self.topics = list(topics)
        self.group_id = group_id
        self.offsets = dict(offsets or {})
        self.idleness_ms = idleness_ms
        self._riched = arity(deser_func) >= 3
        self.block_mode = False    # set by with_columnar_blocks
        self.block_size = 512
        kind, _ = _parse_brokers(brokers)
        if kind != "memory":
            _require_kafka_client()

    def build_replicas(self) -> None:
        self.replicas = [KafkaSourceReplica(self, i)
                         for i in range(self.parallelism)]


class KafkaSourceReplica(BasicReplica):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        self._st_ingest = self.stats.stage("ingest")  # one column block
        # aligned checkpointing (windflow_tpu.checkpoint): barriers inject
        # BETWEEN Kafka messages (never between the pushes of one deser
        # call) so the snapshot offsets cover exactly the shipped prefix
        self._coord = None
        self._inject_cb = None
        self._last_ckpt = 0
        self._restore_offsets: Optional[Dict[Tuple[str, int], int]] = None
        self._transport = None
        # offsets captured at each injected barrier, committed to the
        # broker only when the coordinator finalizes that checkpoint —
        # from THIS thread (consumers are not thread-safe): the finalize
        # listener only flips _commit_ready
        self._pending_commits: Dict[int, Dict[Tuple[str, int], int]] = {}
        self._commit_ready = 0
        self._committed = 0
        # overload admission control (windflow_tpu.overload): installed
        # by the governor while shedding, same contract as
        # SourceReplica._gate (shed before emit; a shed Kafka record's
        # offset is already consumed, so it never replays)
        self._gate = None
        # gate-buffered records caught by a snapshot (their offsets are
        # already consumed — run_source re-emits them after restore)
        self._restore_gate_pending = None

    def process(self, payload, ts, wm, tag):  # pragma: no cover
        raise WindFlowError("Kafka_Source has no input")

    def _note_reconnect(self) -> None:
        """Transport retry hook: one transient-error retry/reconnect
        (``Kafka_reconnects`` / ``windflow_kafka_reconnects_total``)."""
        self.stats.kafka_reconnects += 1

    # -- checkpointing -----------------------------------------------------
    def bind_checkpoint(self, coordinator, inject_cb) -> None:
        self._coord = coordinator
        self._inject_cb = inject_cb
        self._last_ckpt = coordinator.requested_id
        coordinator.add_finalize_listener(self._on_finalized)

    def request_checkpoint(self):
        # injection happens at the consume loop's next message boundary
        return None if self._coord is None \
            else self._coord.trigger(force=True)

    def _on_finalized(self, ckpt_id: int) -> None:
        # runs on another worker's thread: only publish the watermark
        if ckpt_id > self._commit_ready:
            self._commit_ready = ckpt_id

    def _maybe_inject(self) -> None:
        from ..message import Barrier
        cid = self._coord.requested_id
        if cid > self._last_ckpt:
            self._last_ckpt = cid
            if self._transport is not None:
                self._pending_commits[cid] = \
                    self._transport.snapshot_positions()
            self._inject_cb(Barrier(cid))

    def final_checkpoint(self) -> None:
        """Worker hook at consume-loop exit (see SourceReplica): inject a
        pending epoch's barrier with the final offsets before EOS."""
        if self._coord is not None and self._transport is not None:
            if self._coord.requested_id != self._last_ckpt:
                self._maybe_inject()
            self._maybe_commit()

    def _maybe_commit(self) -> None:
        ready = self._commit_ready
        if ready <= self._committed or self._transport is None:
            return
        best = max((c for c in self._pending_commits if c <= ready),
                   default=None)
        if best is not None:
            self._transport.commit_offsets(self._pending_commits[best])
            for c in [c for c in self._pending_commits if c <= best]:
                del self._pending_commits[c]
        self._committed = ready

    def snapshot_state(self) -> dict:
        st = super().snapshot_state()
        if self._transport is not None:
            # keys are (topic, partition) tuples — pickle keeps them
            st["offsets"] = self._transport.snapshot_positions()
        # shed accounting rides the snapshot (same contract as
        # SourceReplica): restore must not zero permanent drops
        st["shed_records"] = self.stats.shed_records
        st["shed_bytes"] = self.stats.shed_bytes
        gate = self._gate
        if gate is not None and gate.pending:
            # records accepted into the gate but still awaiting tokens:
            # their offsets are covered by the snapshot positions above,
            # so they never replay from the broker — they must ride the
            # snapshot or a restore loses them (neither admitted nor
            # shed)
            st["gate_pending"] = gate.snapshot_pending()
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        offs = state.get("offsets")
        if offs is not None:
            self._restore_offsets = dict(offs)
        self._restore_gate_pending = state.get("gate_pending")
        self.stats.shed_records = state.get("shed_records", 0)
        self.stats.shed_bytes = state.get("shed_bytes", 0)

    def run_source(self) -> None:
        op = self.op
        pend = self._restore_gate_pending
        if pend:
            # re-emit the snapshot's gate-buffered records before the
            # consume loop resumes (their offsets never replay); ahead
            # of the subscribe so a no-partition early return cannot
            # drop them
            self._restore_gate_pending = None
            for p, t, w in pend:
                self._advance_wm(w)
                self._emit_admitted(p, t)
        transport = make_transport(op.brokers)
        if self._coord is not None and hasattr(transport, "auto_commit"):
            transport.auto_commit = False  # commits ride checkpoints only
        transport.on_retry = self._note_reconnect
        self._transport = transport
        offsets = op.offsets
        if self._restore_offsets is not None:
            # resume from the checkpoint's recorded positions. The
            # snapshot was taken per replica AFTER the group share split,
            # so it is already this member's slice — subscribe must not
            # re-split it (member 0 of 1): same-parallelism restore maps
            # replica idx -> its own recorded partitions
            offsets = self._restore_offsets
            member, n_members = 0, 1
        else:
            member, n_members = self.idx, op.parallelism
        try:
            if not transport.subscribe(op.topics, op.group_id, member,
                                       n_members, offsets):
                return
            self._consume_loop(transport)
            gate = self._gate
            if gate is not None and gate.pending:
                # end-of-stream with records still buffered in the
                # gate: they were ACCEPTED (only awaiting tokens) —
                # emit before the final barrier injects, mirroring
                # SourceReplica.run_source
                for p, t, w in gate.drain_pending():
                    self._advance_wm(w)
                    self._emit_admitted(p, t)
        finally:
            # the worker's final_checkpoint hook runs after run_source —
            # too late for the transport; inject any pending epoch with
            # the final offsets here, while the consumer is still open
            self.final_checkpoint()
            transport.close()
            self._transport = None

    def _consume_loop(self, transport) -> None:
        op = self.op
        shipper = SourceShipper(self)
        idle_budget_us = op.idleness_ms * 1000
        last_progress = current_time_usecs()
        block_n = op.block_size if op.block_mode else 0
        while True:
            if self._coord is not None:
                if self._coord.requested_id != self._last_ckpt:
                    self._maybe_inject()
                self._maybe_commit()
            if block_n:
                # columnar block mode: one batch poll, the functor
                # decodes the whole list vectorized (push_columns).
                # Barriers land only between polls — the offsets
                # snapshotted at injection cover exactly the blocks
                # already shipped, same cursor semantics as per-message
                msgs = transport.consume_batch(block_n)
                if msgs:
                    last_progress = current_time_usecs()
                    cont = (op.deser_func(msgs, shipper, self.context)
                            if op._riched else op.deser_func(msgs, shipper))
                    if cont is False:
                        return
                    continue
            else:
                msg = transport.consume()
                if msg is not None:
                    last_progress = current_time_usecs()
                    cont = (op.deser_func(msg, shipper, self.context)
                            if op._riched else op.deser_func(msg, shipper))
                    if cont is False:
                        return
                    continue
            if current_time_usecs() - last_progress > idle_budget_us:
                # idle timeout: give the functor a chance to stop
                cont = (op.deser_func(None, shipper, self.context)
                        if op._riched else op.deser_func(None, shipper))
                if cont is False:
                    return
                last_progress = current_time_usecs()
            time.sleep(0.001)

    def ship(self, payload: Any, ts: int, wm: int) -> None:
        gate = self._gate
        if gate is not None:
            # watermark rides each record through the gate (see
            # SourceReplica.ship): a buffered record emits under its
            # accept-time watermark, never one the stream advanced to
            # while it waited
            for p, t, w in gate.offer(payload, ts, wm):
                self._advance_wm(w)
                self._emit_admitted(p, t)
            if gate.released and not gate.pending:
                self._gate = None
            return
        if wm > self.cur_wm:
            self.cur_wm = wm
        self._emit_admitted(payload, ts)

    def _emit_admitted(self, payload: Any, ts: int) -> None:
        st = self.stats
        st.inputs_received += 1
        # sampled latency tracing, same mask gate as SourceReplica.ship
        if not (st.inputs_received & (st.sample_every - 1)):
            self.emitter.trace_ts = current_time_usecs()
        self.emitter.emit(payload, ts, self.cur_wm)

    def ship_columns(self, cols, ts_arr, wm: int) -> None:
        """Columnar twin of ``ship`` (``shipper.push_columns`` lands
        here): same gate / watermark / trace contract as
        ``SourceReplica.ship_columns``, minus barrier injection — in the
        Kafka loop barriers land between polls, never inside a block."""
        # one pushed block, gate to emit, waits included (blk:ingest)
        with self._st_ingest():
            gate = self._gate
            if gate is not None:
                if gate.pending:
                    # row-path records accepted into the gate's buffer
                    # precede this block: emit them first (accept-time
                    # watermarks) or the stream reorders
                    for p, t, w in gate.drain_pending():
                        self._advance_wm(w)
                        self._emit_admitted(p, t)
                if gate.released:
                    self._gate = None
                else:
                    cols, ts_arr, n = gate.offer_columns(cols, ts_arr)
                    if n == 0:
                        return
            if wm > self.cur_wm:
                self.cur_wm = wm
            st = self.stats
            n = len(ts_arr)
            base = st.inputs_received
            st.inputs_received = base + n
            trace_rows = None
            se = st.sample_every
            if se:
                # vectorized mask gate — the cohort the row path would stamp
                first = (-(base + 1)) % se
                if first < n:
                    trace_rows = np.arange(first, n, se)
                    self.emitter.trace_ts = current_time_usecs()
            self.emitter.emit_columns(cols, ts_arr, self.cur_wm, trace_rows)
            st.ingest_rows += n



# ---------------------------------------------------------------------------
# Kafka_Sink
# ---------------------------------------------------------------------------
class Kafka_Sink(BasicOperator):
    """User serializer returns (topic, partition_or_None, payload) or None
    to drop (``kafka_sink.hpp``: wf_kafka_sink_msg)."""

    op_type = OpType.SINK
    # exactly-once mode (windflow_tpu.sinks.transactional): epoch
    # transactions on the broker — prepared at the barrier, committed
    # only on coordinator finalize, zombie producers fenced
    supports_exactly_once = True

    def __init__(self, ser_func: Callable, brokers: str,
                 name: str = "kafka_sink", parallelism: int = 1) -> None:
        super().__init__(name, parallelism, RoutingMode.FORWARD)
        self.ser_func = ser_func
        self.brokers = brokers
        self._riched = arity(ser_func) >= 2
        kind, _ = _parse_brokers(brokers)
        if kind != "memory":
            _require_kafka_client()
        self.exactly_once = False
        self.txn_dir: Optional[str] = None  # staging root (real brokers)

    def build_replicas(self) -> None:
        cls = TxnKafkaSinkReplica if self.exactly_once else KafkaSinkReplica
        self.replicas = [cls(self, i) for i in range(self.parallelism)]


class KafkaSinkReplica(BasicReplica):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        self._transport = make_transport(op.brokers)
        self._transport.on_retry = self._note_reconnect
        # terminal operator: record end-to-end latency of traced tuples
        self._e2e = self.stats.hist_e2e

    def _note_reconnect(self) -> None:
        self.stats.kafka_reconnects += 1

    def process(self, payload, ts, wm, tag):
        out = (self.op.ser_func(payload, self.context) if self.op._riched
               else self.op.ser_func(payload))
        if out is None:
            return
        topic, partition, data = out
        self._transport.produce(topic, data, partition)

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self) -> dict:
        # flush the producer and fail LOUDLY on delivery errors before
        # this worker's ack can let the coordinator count the epoch
        # finalized: a lost in-flight produce used to be silent — the
        # checkpoint then recorded source offsets past data that never
        # reached the broker, and a restart skipped it forever
        self._transport.flush()
        return super().snapshot_state()

    def flush_on_termination(self) -> None:
        self._transport.flush()
        self._transport.close()


# ---------------------------------------------------------------------------
# Exactly-once Kafka sink: epoch transactions driven by the checkpoint
# coordinator (windflow_tpu.sinks.transactional)
# ---------------------------------------------------------------------------
class _MemoryTxnBackend:
    """2PC backend over ``MemoryBroker``'s transaction log: prepared
    epochs live in the broker (they survive the producer's death, like a
    real broker's transaction markers) and zombie generations are fenced
    broker-side."""

    def __init__(self, broker: MemoryBroker, txn_id: str) -> None:
        self.broker = broker
        self.txn_id = txn_id
        self.gen = broker.txn_init(txn_id)

    def check_fence(self) -> None:
        self.broker.txn_check(self.txn_id, self.gen)

    def is_committed(self, epoch: int) -> bool:
        return self.broker.txn_is_committed(self.txn_id, epoch)

    def do_precommit(self, epoch: int, records) -> None:
        self.broker.txn_prepare(self.txn_id, self.gen, epoch, records)

    def do_commit(self, epoch: int):
        self.broker.txn_commit(self.txn_id, self.gen, epoch)
        return None  # no functor delivery: the topic IS the output

    def do_abort(self, epoch: int) -> None:
        self.broker.txn_abort(self.txn_id, self.gen, epoch)

    def do_recover(self, last_epoch: int):
        rolled, aborted = [], []
        for epoch in self.broker.txn_prepared_epochs(self.txn_id):
            if epoch <= last_epoch:
                if self.broker.txn_commit(self.txn_id, self.gen, epoch):
                    rolled.append((epoch, None))
            else:
                self.broker.txn_abort(self.txn_id, self.gen, epoch)
                aborted.append(epoch)
        return rolled, aborted


class _StagedKafkaBackend:
    """Real-broker backend: epochs stage durably in a local
    ``EpochSegmentStore`` (the broker holds nothing until finalize), and
    each commit produces the whole epoch inside one Kafka transaction
    (``txn_produce_epoch``) so ``read_committed`` consumers see epochs
    atomically. The local ``.seg`` rename is the commit marker; the
    window between the broker transaction committing and the rename is
    the one crash window that can duplicate an epoch on roll-forward
    (closing it needs Kafka's resumable-transaction surface, which the
    plain client API does not expose — documented in docs/API.md)."""

    def __init__(self, root: str, transport, txn_id: str) -> None:
        from ..sinks.transactional import SegmentBackend
        self._seg = SegmentBackend(root)
        self.transport = transport
        self.txn_id = txn_id

    def is_committed(self, epoch: int) -> bool:
        return self._seg.is_committed(epoch)

    def do_precommit(self, epoch: int, records) -> None:
        self._seg.do_precommit(epoch, records)

    def do_commit(self, epoch: int):
        import pickle as _pickle
        records = self._seg._records.get(epoch)
        if records is None and not self._seg.is_committed(epoch):
            records = _pickle.loads(self._seg.store.read(epoch,
                                                         pending=True))
        if records:
            self.transport.txn_produce_epoch(self.txn_id, records)
        self._seg.do_commit(epoch)
        return None

    def do_abort(self, epoch: int) -> None:
        self._seg.do_abort(epoch)

    def do_recover(self, last_epoch: int):
        import pickle as _pickle
        self._seg.store.reap_tmp()
        rolled, aborted = [], []
        for epoch in self._seg.store.pending_epochs():
            if epoch <= last_epoch:
                records = _pickle.loads(
                    self._seg.store.read(epoch, pending=True))
                if records:
                    self.transport.txn_produce_epoch(self.txn_id, records)
                self._seg.store.commit(epoch)
                rolled.append((epoch, None))
            else:
                self._seg.store.abort(epoch)
                aborted.append(epoch)
        return rolled, aborted


class TxnKafkaSinkReplica(KafkaSinkReplica):
    """Kafka sink in exactly-once mode: serialized records buffer per
    epoch, prepare on the broker (memory://) or in a local staged
    segment (real brokers) at the barrier, and reach the topic only when
    the coordinator finalizes the epoch. The transactional id
    ``wf-txn-<op>-r<idx>`` is stable across restarts and rebuilds, so
    zombie replicas left unwinding by a rescale are fenced."""

    def __init__(self, op, idx):
        super().__init__(op, idx)
        from ..sinks.transactional import EpochTxnDriver, txn_dir_for
        txn_id = f"wf-txn-{op.name}-r{idx}"
        if isinstance(self._transport, MemoryTransport):
            backend = _MemoryTxnBackend(self._transport.broker, txn_id)
        elif getattr(self._transport, "supports_transactions", False):
            backend = _StagedKafkaBackend(
                txn_dir_for(op.name, idx, op.txn_dir), self._transport,
                txn_id)
        else:
            raise WindFlowError(
                f"{op.name}: exactly-once needs a transactional producer "
                "— use a memory:// broker or confluent_kafka "
                "(kafka-python has no transactions)")
        self._txn = EpochTxnDriver(backend, self.stats)
        self.on_idle = self._txn.poll

    def process(self, payload, ts, wm, tag):
        out = (self.op.ser_func(payload, self.context) if self.op._riched
               else self.op.ser_func(payload))
        if out is None:
            return
        check = getattr(self._txn.backend, "check_fence", None)
        if check is not None:
            try:
                check()
            except FencedWriteError:
                self.stats.txn_fenced_writes += 1
                raise
        topic, partition, data = out
        self._txn.buffer.append((topic, partition, None, data))

    def handle_msg(self, ch, msg):
        t = self._txn
        if t._pending and min(t._pending) <= t._commit_ready:
            t.poll()
        super().handle_msg(ch, msg)

    # -- worker / coordinator hooks ----------------------------------------
    def bind_txn_coordinator(self, coordinator) -> None:
        self._txn.bind(coordinator)

    def precommit_epoch(self, ckpt_id: int) -> None:
        self._txn.precommit_epoch(ckpt_id)

    def snapshot_state(self) -> dict:
        st = BasicReplica.snapshot_state(self)  # no blind producer flush:
        st.update(self._txn.snapshot())  # records ride the epoch txn
        return st

    def restore_state(self, state: dict) -> None:
        BasicReplica.restore_state(self, state)
        self._txn.restore(state)

    def flush_on_termination(self) -> None:
        # EOS: stage the post-barrier tail as one final epoch; it (and
        # any not-yet-finalized epoch) commits in txn_complete once the
        # run is known to have finished cleanly
        self._txn.seal_tail()

    def txn_complete(self) -> None:
        self._txn.complete_all()
        self._transport.flush()
        self._transport.close()
