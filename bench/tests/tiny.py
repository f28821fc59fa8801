"""Small shapes of the benchmark's configurations and mixes, for runs of
the harness on the CPU."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = {
    "transformer": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                    "n_kv_heads": 4, "head_dim": 32, "d_ff": 256,
                    "vocab_size": 2048},
    "rwkv6": {"n_layers": 2, "d_model": 128, "n_heads": 2, "n_kv_heads": 2,
              "head_dim": 64, "d_ff": 256, "vocab_size": 2048},
}


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def config(name: str) -> dict:
    """Small shapes at BER 1e-3. What makes the control fail at full width
    is a precision loss meeting the columns that uncorrectable codewords
    blew up: at 1e-4 a small image holds almost none of them, and with
    512 columns at 1e-3 the control read 0.04-0.19 against the limit 0.1
    (2048 columns: 0.17-0.33)."""
    conf = copy.deepcopy(load("configs", name + ".json"))
    conf["model"].update(SIZES[conf["reference"]])
    conf["deployment"]["ber"] = 1e-3
    return conf


def mix(name: str, rate: float = 40.0) -> dict:
    m = copy.deepcopy(load("traffic", name + ".json"))
    if m["arrivals"]["mode"] == "poisson":
        m["arrivals"]["rate"] = rate
    else:
        m["arrivals"].update(outstanding=6, pool=6)
    m["prompt"] = {"median": 20, "sigma": 0.6, "min": 6, "max": 48}
    m["output"] = {"median": 6, "sigma": 0.5, "min": 3, "max": 12}
    m["serving"].update(slots=3, chunk=16, max_len=60)
    return m


def limits(cell: str) -> dict:
    """The cell's limits file; a cell without one (the batch cell, which
    is not in the benchmark) borrows the chat cell's."""
    path = os.path.join(BENCH, "limits", cell + ".json")
    name = cell if os.path.exists(path) else "olmo1b-static-chat"
    lim = load("limits", name + ".json")
    lim.update(sample=40, min_tokens=6)
    return lim
