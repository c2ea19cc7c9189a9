"""Yahoo Streaming Benchmark (ad-campaign windowed counting) on
windflow_tpu — the last BASELINE.json config.

Classic YSB shape: ad events from Kafka -> filter(view) -> project ->
join ad->campaign (static table) -> per-campaign tumbling-window counts.
The windowed count runs on the device plane (Ffat_Windows_TPU with a
count+latest-ts combine); switch USE_TPU off for the CPU Ffat_Windows.

END-TO-END LATENCY (the YSB metric): every event carries its ingest
wall-clock through the whole pipeline (a relative-µs int32 column on the
device plane); the sink reports p50/p99 of (emit wall - last contributing
event's ingest wall) per fired window, on BOTH planes.

Run: JAX_PLATFORMS=cpu python examples/ysb.py [n_events]
(on a machine with a TPU, leave JAX_PLATFORMS unset; YSB_CPU=1 selects
the CPU window operator.)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dataclasses import dataclass

from windflow_tpu import (ExecutionMode, Filter_Builder, Map_Builder,
                          PipeGraph, Sink_Builder, TimePolicy)
from windflow_tpu.kafka import Kafka_Source_Builder, MemoryBroker

USE_TPU = os.environ.get("YSB_CPU") != "1"
# YSB_DEVICE_CHAIN=1 moves the view-filter and the ad->campaign join onto
# the device plane too (Filter_TPU + Map_TPU ahead of the windows): the
# CPU plane then only runs the per-message Kafka deser, and the whole
# filter/join/window chain is XLA programs over columnar batches.
DEVICE_CHAIN = USE_TPU and os.environ.get("YSB_DEVICE_CHAIN") == "1"
BATCH = int(os.environ.get("YSB_BATCH", "4096"))
TS_STEP_US = 100  # event-time spacing in fill_broker; rate pacing derives the
                  # event index from it (keep the two in sync)
N_CAMPAIGNS = 100
ADS_PER_CAMPAIGN = 10
WIN_US = 10_000_000  # 10s tumbling windows


@dataclass
class AdEvent:
    ad_id: int
    event_type: int  # 0=view 1=click 2=purchase
    ts: int
    ing: int  # ingest wall clock, µs relative to run start


@dataclass
class CampaignEvent:
    campaign: int
    one: int
    ts: int
    ing: int


def fill_broker(n_events: int) -> None:
    b = MemoryBroker.get("ysb", 8)
    for i in range(n_events):
        b.produce("ad_events", {
            "ad_id": i % (N_CAMPAIGNS * ADS_PER_CAMPAIGN),
            "event_type": i % 3,
            "ts": i * TS_STEP_US,
        }, key=i % 8)


def main(n_events: int = 60_000) -> None:
    fill_broker(n_events)
    results = {}
    latencies = []

    graph = PipeGraph("ysb", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    wall0 = time.perf_counter()

    def now_rel() -> int:
        return int((time.perf_counter() - wall0) * 1e6)

    # YSB_RATE=<events/sec> paces ingestion to a fixed aggregate rate (the
    # standard YSB latency protocol measures AT a rate, not at saturation
    # where latency is just queue depth); 0/unset drains flat out.
    rate = float(os.environ.get("YSB_RATE", "0") or 0)

    def deser(msg, shipper):
        if msg is None:
            return False
        p = msg.payload
        if rate > 0:
            target_us = (p["ts"] / TS_STEP_US) / rate * 1e6  # index/rate
            lag = target_us - now_rel()
            while lag > 500:
                time.sleep(min(0.005, lag / 1e6))
                lag = target_us - now_rel()
        shipper.push_with_timestamp(
            AdEvent(p["ad_id"], p["event_type"], p["ts"], now_rel()),
            p["ts"])
        shipper.set_next_watermark(p["ts"])
        return True

    src = (Kafka_Source_Builder(deser).with_brokers("memory://ysb")
           .with_topics("ad_events").with_idleness(100)
           .with_parallelism(2)
           .with_output_batch_size(BATCH if USE_TPU else 0).build())
    if DEVICE_CHAIN:
        from windflow_tpu.tpu import Filter_TPU_Builder, Map_TPU_Builder
        views = (Filter_TPU_Builder(lambda f: f["event_type"] == 0)
                 .build())
        # ad -> campaign join on device (static-table join = int division
        # here; a general table is one device-LUT gather)
        project = (Map_TPU_Builder(
                       lambda f: {"campaign": f["ad_id"] // ADS_PER_CAMPAIGN,
                                  "one": f["event_type"] * 0 + 1,
                                  "ing": f["ing"]})
                   .build())
    else:
        views = (Filter_Builder(lambda e: e.event_type == 0)
                 .with_parallelism(2)
                 .with_output_batch_size(BATCH if USE_TPU else 0).build())
        # ad -> campaign join against the static campaign table
        project = (Map_Builder(lambda e: CampaignEvent(
                       e.ad_id // ADS_PER_CAMPAIGN, 1, e.ts, e.ing))
                   .with_parallelism(2)
                   .with_output_batch_size(BATCH if USE_TPU else 0).build())

    if USE_TPU:
        from windflow_tpu.tpu import Ffat_Windows_TPU_Builder
        win = (Ffat_Windows_TPU_Builder(
                   lambda f: {"count": f["one"], "last_ing": f["ing"]},
                   lambda a, b: {"count": a["count"] + b["count"],
                                 "last_ing": b["last_ing"]})
               .with_key_by("campaign")
               .with_tb_windows(WIN_US, WIN_US)
               .with_num_win_per_batch(32)
               .with_key_capacity(N_CAMPAIGNS).build())

        def sink(cols, ts):
            # with_columns exit: whole fired-window batches, no per-row
            # boxing (the round-5 columnar sink edge)
            if cols is None:
                return
            now = now_rel()
            v = cols["valid"].astype(bool)
            for c, w, n in zip(cols["campaign"][v].tolist(),
                               cols["wid"][v].tolist(),
                               cols["count"][v].tolist()):
                results[(c, w)] = n
            latencies.extend((now - cols["last_ing"][v]).tolist())
    else:
        from windflow_tpu import Ffat_Windows_Builder
        # lift to (count, last_ingest): the CPU FlatFAT combines tuples
        win = (Ffat_Windows_Builder(lambda e: (e.one, e.ing),
                                    lambda a, b: (a[0] + b[0], b[1]))
               .with_key_by(lambda e: e.campaign)
               .with_tb_windows(WIN_US, WIN_US).build())

        def sink(r):
            if r is not None and r.value is not None:
                results[(r.key, r.wid)] = r.value[0]
                latencies.append(now_rel() - r.value[1])

    sink_b = (Sink_Builder(sink).with_columns() if USE_TPU
              else Sink_Builder(sink))
    graph.add_source(src).add(views).add(project).add(win).add_sink(
        sink_b.build())

    t0 = time.perf_counter()
    graph.run()
    dt = time.perf_counter() - t0

    # model check
    expected = {}
    for i in range(n_events):
        if i % 3 == 0:
            c = (i % (N_CAMPAIGNS * ADS_PER_CAMPAIGN)) // ADS_PER_CAMPAIGN
            w = (i * TS_STEP_US) // WIN_US
            expected[(c, w)] = expected.get((c, w), 0) + 1
    ok = results == expected
    import math
    lat = sorted(latencies)
    p50 = lat[len(lat) // 2] / 1e3 if lat else 0.0
    p99 = (lat[min(len(lat) - 1, max(0, math.ceil(len(lat) * 0.99) - 1))]
           / 1e3 if lat else 0.0)  # nearest-rank
    print(f"YSB [{'TPU' if USE_TPU else 'CPU'}]: {n_events} events in "
          f"{dt:.2f}s ({n_events/dt:,.0f} ev/s), "
          f"{len(results)} campaign-windows, model match: {ok}, "
          f"e2e latency p50={p50:.1f}ms p99={p99:.1f}ms "
          f"(source ingest -> window emit)")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 60_000)
