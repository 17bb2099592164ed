"""A deployment small enough for the CPU, with the cells' real mixes."""
from __future__ import annotations

import copy
import json
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def tiny_cell(traffic: str, rate: float = 60.0, n_users: int = 400,
              n_items: int = 160) -> types.SimpleNamespace:
    """``ml1m``'s configuration at ``n_users`` x ``n_items``, under the
    named traffic mix, as ``run.load_cell`` would give it."""
    cfg = json.loads((BENCH / "configs" / "ml1m.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["data"].update(n_users=n_users, n_items=n_items,
                       n_ratings=n_users * 30, max_per_user=n_items // 2)
    cfg["serving"]["capacity"] = 1024
    cfg["engine"]["max_batch"] = 32
    cfg["check"] = {"pair": 200, "topn": 50}
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    if mix["loop"] == "closed":
        mix["mix"][0]["rows"] = [8, 8]
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return types.SimpleNamespace(
        name=f"tiny.{traffic}", cell={"chips": 1}, cfg=cfg, mix=mix,
        load={"rate_per_s": rate}, end_to_end=bench["end_to_end"],
        per_layer=bench["per_layer"])
