"""Hot set: mean over the window's summarized answers of
``QueryStats.num_hot / num_nodes`` (the paper's vertex ratio)."""


def read(record):
    rows = [a for a in record["answers"]
            if a["action"] == "compute-approximate"]
    if not rows:
        return None
    return sum(a["num_hot"] / max(a["num_nodes"], 1) for a in rows) / len(rows)
