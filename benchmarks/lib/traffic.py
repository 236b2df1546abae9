"""The one general traffic generator.  A traffic mix is a data file under
``benchmarks/traffic/`` (parameters only); a configuration says which columns
exist and how large each vocabulary is.  The generator draws the rows of one
epoch from ``--seed`` and writes them in the on-disk format the program's own
preprocessing leaves behind (``data/criteo_preprocessing.py``,
``data/ctr_preprocessing.py``): ``parquet/train_part_<k>.parquet`` with int32
categorical columns, float32 continuous columns and an int8 ``label``, plus
``size_map.json``.

Every seed gives the same number of rows, columns and files: only the values
differ, so the seed does not change the amount of work."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ID_DISTRIBUTIONS = ("uniform", "zipf")


def draw_ids(rng: np.random.Generator, n: int, vocab: int, spec: dict
             ) -> np.ndarray:
    """``n`` ids in ``[0, vocab)``.  ``uniform``: every row equally likely
    (the most distinct rows a batch can touch).  ``zipf``: rank ``r`` drawn
    with probability ~ ``r**-exponent`` (inverse CDF of the continuous power
    law), then ranks are spread over the vocabulary by a fixed odd multiplier
    so that hot rows are not neighbours."""
    kind = spec.get("distribution", "uniform")
    if kind == "uniform":
        return rng.integers(0, vocab, n, dtype=np.int64).astype(np.int32)
    if kind == "zipf":
        a = float(spec["exponent"])
        u = rng.random(n)
        if abs(a - 1.0) < 1e-9:
            rank = np.exp(u * np.log(vocab + 1.0))
        else:
            top = (vocab + 1.0) ** (1.0 - a)
            rank = (1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - a))
        rank = np.clip(rank.astype(np.int64) - 1, 0, vocab - 1)
        return ((rank * 2654435761) % vocab).astype(np.int32)
    raise ValueError(f"traffic: unknown id distribution {kind!r}; known: "
                     f"{ID_DISTRIBUTIONS}")


def draw_labels(rng, n: int, spec: dict) -> np.ndarray:
    """``bernoulli``: a coin of the given ``rate``, independent of the row."""
    kind = spec.get("kind", "bernoulli")
    if kind != "bernoulli":
        raise ValueError(f"traffic: unknown label kind {kind!r}")
    return (rng.random(n) < float(spec.get("rate", 0.5))).astype(np.int8)


def draw_rows(seed: int, n: int, *, columns: dict, traffic: dict
              ) -> dict[str, np.ndarray]:
    """One epoch's rows.  ``columns``: ``{"categorical": {column: vocab},
    "continuous": [column, ...]}`` from the configuration."""
    rng = np.random.default_rng([int(seed), 0x7A])
    ids = traffic.get("ids", {})
    cols: dict[str, np.ndarray] = {}
    for c in columns["continuous"]:
        cols[c] = rng.random(n, dtype=np.float32)
    cols["label"] = draw_labels(rng, n, traffic["label"])
    for c, vocab in columns["categorical"].items():
        cols[c] = draw_ids(rng, n, int(vocab), {**ids, **ids.get("per_column", {}).get(c, {})})
    return cols


def write_epoch(data_dir: Path, rows: dict[str, np.ndarray], size_map: dict,
                *, files: int) -> None:
    """Contiguous row ranges, one per file: the loader shuffles files and rows
    itself from (seed, epoch)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    data_dir = Path(data_dir)
    (data_dir / "parquet").mkdir(parents=True, exist_ok=True)
    (data_dir / "size_map.json").write_text(json.dumps(size_map, indent=4))
    n = len(rows["label"])
    cuts = np.linspace(0, n, files + 1).astype(int)
    for k, (a, b) in enumerate(zip(cuts, cuts[1:])):
        pq.write_table(pa.table({c: v[a:b] for c, v in rows.items()}),
                       data_dir / "parquet" / f"train_part_{k}.parquet")


def row_keys(rows: dict[str, np.ndarray], columns: list[str]) -> np.ndarray:
    """One uint64 per row from its categorical ids: enough to tell whether a
    row the program fed its step is one the generator wrote."""
    key = np.zeros(len(rows[columns[0]]), np.uint64)
    for c in columns:
        key = (key * np.uint64(0x100000001B3)) ^ rows[c].astype(np.uint64)
        key = key ^ (key >> np.uint64(29))
    return key
