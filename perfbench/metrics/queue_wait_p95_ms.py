"""queue_wait_p95_ms: ServeMetrics: replica-side submit until admitted to a slot, 95th percentile over the replica's last 512 admissions, read at window end."""


def read(ctx):
    v = ctx["program"]["stats1"].get("ttft_queue_p95_s")
    return None if v is None else 1000.0 * v
