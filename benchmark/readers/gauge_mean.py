"""Mean over the window, in percent, of the program's gauge
``args.gauge`` (a share between 0 and 1), sampled 20 times a second."""


def gauges(metric: dict) -> dict:
    a = metric["args"]
    return {a["gauge"]: (a["gauge"], a.get("labels", {}))}


def read(metric: dict, ctx: dict):
    seen = ctx["window"].get("samples", {}).get(metric["args"]["gauge"])
    if not seen:
        return None
    return 100.0 * sum(seen) / len(seen)
