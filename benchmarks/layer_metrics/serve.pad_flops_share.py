"""Mean over the window's dispatches of the cubic share of each padded
stack that is padding (`batch.padding_waste_flops`), in percent."""


def compute(run):
    h = run["histograms"].get("batch.padding_waste_flops")
    if not h or not h["count"]:
        return None
    return 100.0 * h["mean"]
