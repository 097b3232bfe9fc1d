"""How ``correct`` is decided, and the chip's peaks.

Once the window has closed and the program's state is freed, a sample of
the greedy requests the window finished — drawn from the seed, and
always holding the longest of them — is run again through the plain
float32 reference (``bench/configs/<reference>.py``), prompt followed by
the tokens the program served. At every served position the reference's
logits give the gap by which the served token lies below the reference's
best token; a sound program serves a token at or next to the top, so
the widest gap stays at the rounding level of bfloat16 serving. The
cell's file gives the limit and the readings it was set from.

The control (``control=True``, used by ``bench/calibrate.py`` and the
tests, never by a benchmark run) is the same reference computed with
every matmul operand rounded to float8: at each position its top token
is scored by the float32 reference in the same way, and judged in the
served token's place.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HEAD_BLOCK = 512        # served positions per head call of the reference


def load_peaks(root: Path) -> Dict[str, dict]:
    """The table of chip peaks, keyed by JAX's ``device_kind``."""
    return json.loads((Path(root) / "peaks.json").read_text())["devices"]


def peaks_for(table: Dict[str, dict], device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``; a chip not in the table is an error."""
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def sample_greedy(specs, sequences, seed: int,
                  n: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Up to ``n`` finished greedy requests as (prompt, served tokens):
    the one with the most positions, and the rest drawn from the seed."""
    done = {s.rid: s for s in sequences if s.t_done is not None}
    greedy = [sp for sp in specs if sp.greedy and sp.rid in done
              and len(done[sp.rid].generated) == sp.max_new_tokens]
    if not greedy:
        return []
    greedy.sort(key=lambda sp: (-sp.total_len, sp.rid))
    rest = np.random.default_rng([seed, 1]).permutation(len(greedy) - 1)
    pick = [greedy[0]] + [greedy[1 + i] for i in rest[:n - 1]]
    return [(np.asarray(sp.prompt, np.int32),
             np.asarray(done[sp.rid].generated, np.int32)) for sp in pick]


def reference_gaps(arch, cfg: dict, seed: int,
                   sample: Sequence[Tuple[np.ndarray, np.ndarray]],
                   batch: int, control: bool = False
                   ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Gaps of the served tokens (and, with ``control``, of the float8
    control's top tokens) below the float32 reference's best logit, one
    per served position of the sample.

    The sample is packed into ``batch`` rows of the configuration's
    ``max_seq`` positions (one compiled shape whatever the lengths); the
    reference runs layer by layer on the first device, regenerating each
    layer's weights from the seed, so it never holds more than one."""
    import jax
    import jax.numpy as jnp
    seq_len = cfg["engine"]["max_seq"]
    tokens = np.zeros((batch, seq_len), np.int32)
    rows, pos, served = [], [], []
    for b, (prompt, gen) in enumerate(sample):
        seq = np.concatenate([prompt, gen[:-1]])
        tokens[b, :len(seq)] = seq
        rows += [b] * len(gen)
        pos += list(range(len(prompt) - 1, len(prompt) - 1 + len(gen)))
        served += gen.tolist()
    n = len(served)
    pad = -n % HEAD_BLOCK
    rows, pos = np.array(rows + [0] * pad), np.array(pos + [0] * pad)
    served = np.array(served + [0] * pad, np.int32)
    key = jax.random.key(seed)
    n_layers = arch.dims(cfg)["L"]

    def hidden(fp8: bool):
        h = jax.jit(lambda k, t: arch.embed(k, cfg, t))(key, tokens)
        step = jax.jit(lambda k, i, x: arch.layer(k, cfg, i, x, fp8))
        for i in range(n_layers):
            h = step(key, i, h)
        return h[rows, pos]

    def head(h, toks, fp8: bool):
        fn = jax.jit(lambda k, x, t: _score(arch.logits(k, cfg, x, fp8), t))
        outs = [fn(key, h[i:i + HEAD_BLOCK], toks[i:i + HEAD_BLOCK])
                for i in range(0, len(toks), HEAD_BLOCK)]
        return [np.concatenate([np.asarray(o[j]) for o in outs])
                for j in range(3)]

    def _score(z, toks):
        return (jnp.max(z, -1),
                jnp.take_along_axis(z, toks, axis=-1),
                jnp.argmax(z, -1).astype(jnp.int32))

    with jax.default_matmul_precision("highest"):
        cand = served[:, None]
        if control:
            _, _, top8 = head(hidden(True), cand, True)
            cand = np.stack([served, top8], axis=1)
        best, got, _ = head(hidden(False), cand, False)
    gaps = (best[:, None] - got)[:n]
    return gaps[:, 0], (gaps[:, 1] if control else None)


def run_checks(cell, seed: int, sample, data, window_compiles: int,
               control: bool = False) -> Tuple[dict, dict]:
    """Every number the run is judged correct by, each beside its limit —
    the widest logit gap of the sampled greedy tokens, requests that
    never completed, programs built inside the window — and the readings
    behind the first. With ``control`` the gap judged is that of the
    float8 control's top tokens, in the program's place."""
    lim = cell.load["check"]
    readings = {"served_positions": 0,
                "sample_lengths": [int(len(p) + len(g)) for p, g in sample]}
    if sample:
        gaps, ctrl = reference_gaps(cell.arch(), cell.config, seed, sample,
                                    lim["sample"], control=control)
        readings.update(served_positions=int(len(gaps)),
                        program_widest_gap=float(gaps.max()))
        widest = float(gaps.max())
        if control:
            readings.update(control_widest_gap=float(ctrl.max()),
                            control_top1_agree=float(np.mean(ctrl == 0.0)))
            widest = float(ctrl.max())
    else:
        widest = float("inf")       # nothing greedy finished: not correct
    checks = {"widest_logit_gap": {"value": widest,
                                   "limit": lim["widest_logit_gap"]},
              "requests_failed": {
                  "value": sum(1 for r in data.requests if not r.completed),
                  "limit": 0},
              "window_compiles": {"value": window_compiles, "limit": 0}}
    return checks, readings
