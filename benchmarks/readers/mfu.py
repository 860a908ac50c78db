"""End-to-end model FLOP/s utilisation, in percent: tokens per second
of the window times the family's FLOPs per training token, over the
chips' bf16 peak from ``peaks.json``.  Not a kernel's roofline share."""


def read(run):
    s, dev = run["samples"], run["device"]
    if not s.get("window_s") or s.get("tokens") is None:
        return None
    flops = run["family"].train_flops_per_token(
        run["config"], run["traffic"]["seq"])
    peak = run["peaks"][dev["kind"]]["bf16_flops_per_s"] * dev["count"]
    return 100.0 * s["tokens"] / s["window_s"] * flops / peak
