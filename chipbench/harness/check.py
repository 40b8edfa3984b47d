"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, the plain
reference (the configuration's ``reference`` module) runs once over each
sampled request: its prompt followed by the tokens the server streamed,
the whole sequence causally in float32. At the position that produced
each served token it reads how far that token's logit lies below the
reference's best logit there. The widest such gap over the sample is the
number compared: greedy decoding that followed the model up to its own
rounding gives gaps at the size of near-ties; a wrong token, a wrong
cache entry or a wrong weight gives gaps at the size of the logits'
spread.

The same pass can run a control in the reference's place at a lower
precision: at the same positions it reads the gap (under the float32
reference) of the token the lower precision puts first.

The reference runs layer by layer: each layer's weights are made again
from the seed (``harness.weights.layer``), dequantized, applied to every
sampled sequence, and dropped.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W


@dataclasses.dataclass
class Sample:
    prompt: np.ndarray       # int32 (L,)
    served: np.ndarray       # int32 (n,) tokens the server returned


def draw(records: Sequence[Any], n: int, seed: int) -> List[int]:
    """Indices of ``n`` finished records drawn from the seed, always with
    the one that has the most tokens (prompt plus output)."""
    ok = [i for i, r in enumerate(records) if r.ok]
    if not ok:
        return []
    longest = max(ok, key=lambda i: (len(records[i].prompt)
                                     + len(records[i].served), -i))
    rest = [i for i in ok if i != longest]
    rng = np.random.default_rng([int(seed) & ((1 << 64) - 1), 7])
    pick = rng.permutation(len(rest))[: max(n - 1, 0)]
    return sorted([longest] + [rest[j] for j in pick])


def _pad_len(samples: Sequence[Sample], multiple: int = 128) -> int:
    need = max(len(s.prompt) + len(s.served) - 1 for s in samples)
    return -(-need // multiple) * multiple


def gaps(ref, dm: W.Dims, seed: int, samples: Sequence[Sample],
         control: Optional[str] = None,
         max_len: Optional[int] = None) -> Dict[str, Any]:
    """Run the reference (and optionally a control) over ``samples``.

    Returns ``max_gap`` (widest served-token gap), ``gaps`` (per sample),
    ``tokens`` (served tokens compared) and, with a control,
    ``control_max_gap`` and ``control_gaps``. Sequences are padded at the
    end (causal, so padding changes no earlier position) to ``max_len``,
    one compiled shape for every run of a configuration."""
    S = max(_pad_len(samples), max_len or 0)
    seqs = []
    for s in samples:
        toks = np.concatenate([s.prompt, s.served[:-1]]).astype(np.int32)
        seqs.append(np.pad(toks, (0, S - len(toks))))
    wq_fn = None if control is None else ref.CONTROLS[control]

    def weights(lw, quant):
        w = {n: ref.dequant(lw[n], W.CONTRACT[n]) for n in W.CONTRACT}
        if quant is not None:
            w = {n: quant(w[n], W.CONTRACT[n]) for n in w}
        return dict(w, ln1=lw["ln1"], ln2=lw["ln2"])

    @jax.jit
    def run_layer(xs, lw):
        w = weights(lw, None)
        return [ref.layer(x, w, dm.rope_theta, dm.eps) for x in xs]

    @jax.jit
    def run_control(xs, lw):
        w = weights(lw, wq_fn)
        return [ref.layer(x, w, dm.rope_theta, dm.eps) for x in xs]

    outer = W.outer(dm, seed)
    xs = [ref.embed(jnp.asarray(t), outer["embed"]) for t in seqs]
    xc = list(xs) if control is not None else None
    # a few sequences per call: one compiled program per run, and the
    # attention scores of one block of sequences at a time in memory
    block = max(1, min(len(xs), (1 << 22) // (S * 8)))
    for i in range(dm.layers):
        lw = W.layer(dm, seed, i)
        xs = [y for j in range(0, len(xs), block)
              for y in run_layer(xs[j:j + block], lw)]
        if control is not None:
            xc = [y for j in range(0, len(xc), block)
                  for y in run_control(xc[j:j + block], lw)]
        del lw

    # every position of the padded sequence is scored and the served
    # span [L - 1, L - 1 + n) masked in, so each request, whatever its
    # lengths, runs the same compiled program
    @jax.jit
    def score(x, xc, ln, hd, served, lo, hi):
        lg = ref.logits(x, ln, hd, dm.eps)
        best = jnp.max(lg, axis=-1)
        p = jnp.arange(S)
        span = (p >= lo) & (p < hi)

        def gap_of(tok):
            g = best - jnp.take_along_axis(lg, tok[:, None], axis=1)[:, 0]
            return jnp.max(jnp.where(span, g, -jnp.inf))
        gc = gap_of(jnp.argmax(ref.logits(xc, ln, hd, dm.eps), axis=-1)) \
            if xc is not None else None
        return gap_of(served), gc

    out: Dict[str, Any] = {"gaps": [], "tokens": 0}
    if control is not None:
        out["control_gaps"] = []
    for j, s in enumerate(samples):
        L, n = len(s.prompt), len(s.served)
        served = np.zeros((S,), np.int32)
        served[L - 1:L - 1 + n] = s.served
        g, gc = score(xs[j], xc[j] if control is not None else None,
                      outer["ln_f"], outer["head"], served, L - 1, L - 1 + n)
        out["gaps"].append(float(g))
        out["tokens"] += n
        if control is not None:
            out["control_gaps"].append(float(gc))
    out["max_gap"] = max(out["gaps"]) if out["gaps"] else float("nan")
    if control is not None:
        out["control_max_gap"] = max(out["control_gaps"])
    return out
