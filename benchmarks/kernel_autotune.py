"""Kernel autotuner gate: the tuned ``dplr_corpus_score`` tile must beat
the fixed default, with oracle parity on EVERY swept configuration.

``repro.kernels.autotune.tune_corpus_score`` sweeps ``block_n`` (and
bf16 accumulation when the slab dtype is bf16) per ``(n, rho, k, Bq, K,
dtype, backend)`` cell and registers the winner so every call site that
leaves ``block_n=None`` inherits it.  This driver pins the claims to CI:

  * **parity everywhere** — every swept (block_n, acc_dtype) candidate
    passes its ref-oracle gate (``dplr_corpus_topk_ref``): f32 candidates
    bit-exact on indices and epsilon-close on values; a failed candidate
    would be recorded and excluded, and FAILS this benchmark — the sweep
    space itself must be safe, not just the winner;
  * **tuned beats default** — on the swept cell (n=3072, rho=2, k=4,
    Bq=1, K=8: a corpus the fixed ``blocks.CORPUS_TILE_N`` pads to 4096
    in two grid steps, where the 4096 candidate clamps to one step with
    no padding) the winner's best-of-repeats time beats the default tile
    by >= 5%.  Candidates
    and the default obey the scoped-VMEM rule
    (``blocks.corpus_vmem_tile``: rows x block_n <= 32768) that every
    ``block_n=None`` call is capped by, so the
    sweep times only tiles serving can run;
  * **registry wiring** — after the sweep, ``blocks.corpus_tile`` (what
    ``ops.dplr_corpus_score`` consults when ``block_n=None``) resolves
    the cell to the registered winner, and a ``block_n=None`` call
    returns bit-identical output to the explicit winner tile;
  * **clamp visibility** — a candidate larger than the corpus is clamped
    by ``blocks.clamp_tile`` and the clamp surfaces as a drained event
    on the sweep result (the "no silent caps" rule), never a crash.

The full (non-quick) run adds a second f32 cell (n=2500, ragged) and a
bf16-slab cell whose sweep includes bf16 accumulation (tolerance-gated
against the f32 oracle; see the autotuner docstring for the gate).

Timing caveat: on the CPU interpret backend the measured microseconds
are Python-loop dominated — larger tiles win because they cut grid
steps, which is the same lever (fewer kernel invocations, better slab
reuse) that decides on real hardware; treat the printed speedups as
gate evidence, not TPU projections.

Output lines:
    kernel_autotune: cell,n=<n>,rho=<r>,k=<k>,Bq=<b>,K=<K>,dtype=<dt>,backend=<be>
    kernel_autotune: sweep,block_n=<bn>,acc=<dt>,us=<t>,parity=<ok|FAIL:reason>
    kernel_autotune: winner,block_n=<bn>,acc=<dt>,us=<t>,default_us=<d>,speedup=<s>x,<ok|FAIL>
    kernel_autotune: wiring,resolved=(<bn>,<dt>),bitexact=<True|False>,<ok|FAIL>
    kernel_autotune: clamp,n=<n>,requested=<bn>,effective=<n>,events=<c>,<ok|FAIL>
The driver exits nonzero unless every gate line ends ``ok``.
"""
from __future__ import annotations

import numpy as np

# the CI-gated cell: probed so the tuned tile beats the fixed default
# with margin on the CPU interpret backend CI runs on (1.61x there; at
# n=6144 or 10240, whole multiples of the default, the default ties or
# wins, which is a legitimate sweep outcome but not a gate)
QUICK_CELL = dict(n=3072, rho=2, k=4, Bq=1, K=8)
QUICK_CANDIDATES = (1024, 4096)
MIN_SPEEDUP = 1.05
REPEATS = 5


def _sweep_cell(cell, candidates, *, dtype="float32", gate_speedup=True):
    """Tune one cell, print its lines, and return (all_parity, beat)."""
    import jax

    from repro.kernels import autotune, blocks, ops

    backend = jax.default_backend()
    print(f"kernel_autotune: cell,n={cell['n']},rho={cell['rho']},"
          f"k={cell['k']},Bq={cell['Bq']},K={cell['K']},dtype={dtype},"
          f"backend={backend}", flush=True)
    tuned = autotune.tune_corpus_score(
        cell["n"], cell["rho"], cell["k"], cell["Bq"], cell["K"],
        dtype=dtype, candidates=candidates, repeats=REPEATS)
    all_parity = True
    for r in tuned.swept:
        all_parity &= r.parity_ok
        tag = "ok" if r.parity_ok else f"FAIL:{r.parity_error}"
        print(f"kernel_autotune: sweep,block_n={r.block_n},"
              f"acc={r.acc_dtype},us={r.us:.1f},parity={tag}", flush=True)
    beat = tuned.speedup >= MIN_SPEEDUP if gate_speedup else True
    print(f"kernel_autotune: winner,block_n={tuned.block_n},"
          f"acc={tuned.acc_dtype},us={tuned.us:.1f},"
          f"default_us={tuned.default_us:.1f},"
          f"speedup={tuned.speedup:.2f}x,"
          f"{'ok' if (all_parity and beat) else 'FAIL'}", flush=True)

    # registry wiring: what block_n=None resolves to IS the winner, and
    # the resolved call is bit-identical to the explicit winner tile
    got = blocks.corpus_tile(cell["n"], cell["rho"], cell["k"],
                             cell["Bq"], cell["K"], dtype, backend)
    wired = got == (tuned.block_n, tuned.acc_dtype)
    Q, a, e, P, aC, valid = autotune._mk_inputs(
        cell["n"], cell["rho"], cell["k"], cell["Bq"], dtype, seed=0)
    v_auto, i_auto = ops.dplr_corpus_score(
        Q, a, e, P, aC, valid=valid, topk=cell["K"])
    v_exp, i_exp = ops.dplr_corpus_score(
        Q, a, e, P, aC, valid=valid, topk=cell["K"],
        block_n=tuned.block_n, acc_dtype=tuned.acc_dtype)
    bitexact = (np.array_equal(np.asarray(v_auto), np.asarray(v_exp))
                and np.array_equal(np.asarray(i_auto), np.asarray(i_exp)))
    wired &= bitexact
    print(f"kernel_autotune: wiring,resolved={got},bitexact={bitexact},"
          f"{'ok' if wired else 'FAIL'}", flush=True)
    return all_parity and beat, wired


def _clamp_leg():
    """A candidate tile larger than the corpus clamps VISIBLY."""
    from repro.kernels import autotune

    n = 1024
    tuned = autotune.tune_corpus_score(n, 2, 4, 1, 8,
                                       candidates=(2048,), repeats=2,
                                       register=False)
    over = [r for r in tuned.swept if r.block_n > n]
    events = sum(len(r.clamps) for r in over)
    ok = (bool(over) and events > 0
          and all(r.effective_block_n == n and r.parity_ok for r in over))
    print(f"kernel_autotune: clamp,n={n},requested=2048,effective="
          f"{over[0].effective_block_n if over else '?'},events={events},"
          f"{'ok' if ok else 'FAIL'}", flush=True)
    return ok


def main(quick: bool = False) -> None:
    from repro.kernels import autotune, blocks

    autotune.clear_results()
    blocks.clear_tuned_tiles()

    ok1, wired1 = _sweep_cell(QUICK_CELL, QUICK_CANDIDATES)
    clamp_ok = _clamp_leg()
    gates = {"sweep": ok1, "wiring": wired1, "clamp": clamp_ok}

    if not quick:
        ragged = dict(QUICK_CELL, n=2500)
        ok2, wired2 = _sweep_cell(ragged, QUICK_CANDIDATES)
        gates["sweep_ragged"] = ok2
        gates["wiring_ragged"] = wired2
        # bf16 slab: the sweep adds bf16 accumulation, tolerance-gated
        # against the f32 oracle; no speedup gate (interpret-mode bf16
        # timing is noise) — the gate is that parity holds everywhere
        okb, wiredb = _sweep_cell(dict(QUICK_CELL, n=4096),
                                  (2048, 4096), dtype="bfloat16",
                                  gate_speedup=False)
        gates["sweep_bf16"] = okb
        gates["wiring_bf16"] = wiredb

    if not all(gates.values()):
        raise SystemExit(
            "kernel_autotune gates violated: "
            + " ".join(f"{k}={v}" for k, v in gates.items()))


if __name__ == "__main__":
    import sys

    main(quick="--quick" in sys.argv)
