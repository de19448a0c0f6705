"""flash_roofline_pct.train: Device trace: the least time the chip could take for the flash forward and backward of the traced steps (the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, both from shapes) over the summed device time of the Mosaic flash kernels."""


def read(ctx):
    from pb import xplane

    t, tr = ctx["trace"], ctx["program"].get("trace")
    if not t or not t.get("devices") or ctx["peaks"] is None or not tr:
        return None
    kernel_s = xplane.match_seconds(t["op_seconds"], ctx["params"]["match"])
    if kernel_s <= 0:
        return None
    d, mix = ctx["dims"], ctx["mix"]
    steps = tr["dispatches"] * int(mix["steps_per_execution"])
    cost = ctx["costs"].flash_train_cost(int(mix["per_chip_batch"]), d["heads"], int(mix["seq"]), d["head_dim"], d["layers"])
    floor = ctx["costs"].roofline_seconds(cost["flops"] * steps, cost["bytes"] * steps, ctx["peaks"])
    print(f"flash kernels: {kernel_s:.4f} s on the device over {steps} steps; floor {floor['seconds']:.4f} s, {floor['bound']}-bound", flush=True)
    return 100.0 * floor["seconds"] / kernel_s
