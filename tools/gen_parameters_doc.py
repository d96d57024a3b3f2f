#!/usr/bin/env python
"""Generate docs/Parameters.md from the parameter schema.

The schema (lightgbm_tpu/params_schema.py) is the single source of truth
extracted from the reference's config doc comments
(reference: include/LightGBM/config.h, rendered as docs/Parameters.rst);
this renders the same surface for lightgbm_tpu users. Re-run after any
schema change: python tools/gen_parameters_doc.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lightgbm_tpu.params_schema import PARAMS  # noqa: E402

HEADER = """# Parameters

All training, IO and prediction parameters, matching the reference
LightGBM v2.3.1 surface (aliases included). Pass them as the `params`
dict of the Python/R APIs, or as `key=value` pairs to the CLI.

Generated from `lightgbm_tpu/params_schema.py` by
`tools/gen_parameters_doc.py` — edit the schema, not this file.

TPU-specific runtime knobs (environment variables, not params): see
`docs/DESIGN.md` (`LGBM_TPU_STRATEGY`, `LGBM_TPU_PALLAS`,
`LGBM_TPU_DP_REDUCE`, `LGBM_TPU_HOST_LEARNER`). Fault-tolerance
knobs (`on_nonfinite`, `resume`, `snapshot_keep`, `checkpoint_freq`,
and the `LGBM_TPU_FAULT_SPEC` / `LGBM_TPU_COLLECTIVE_RETRIES` env
vars): see `docs/Reliability.md`. Observability knobs (`telemetry` and
the `LGBM_TPU_TELEMETRY` / `LGBM_TPU_TRACE_RING` env vars): see
`docs/Observability.md`. Out-of-core streaming knobs (`stream_mode`,
`stream_chunk_rows`, `goss_working_set`): see `docs/Streaming.md`.

| Parameter | Default | Aliases | Constraints | Description |
|---|---|---|---|---|
"""

FOOTER = """
## Growth strategy × `quantized_grad`

How the quantized-gradient pipeline maps onto each growth strategy
(`LGBM_TPU_STRATEGY`; `auto` = masked below 64k rows, compact above):

| Strategy / learner | Working-row gh section | Leaf re-quantization (`quant_renew`) | Histogram collective |
|---|---|---|---|
| `masked` | — (no row buffer; int32 pool, dequantized scans) | no (fixed root scale) | — |
| `compact` / `chunk`, serial | ONE packed `(qg<<16\\|qh)` u32 word (vs three bitcast f32 words) | yes | — |
| device data-parallel (psum) | packed word + 0/1 weight word (pads fenced off the count lane) | yes | exact int32 psum |
| device data-parallel (scatter) | as psum | yes | two-lane `[sum_qg, sum_qh]` reduce-scatter: int16 wire when `quant_max * N <= 32767`, else int32; counts hessian-reconstructed |
| feature-/voting-parallel | host-loop learners carry the quantized pipeline (device variants decline quantized configs) | no | int32 elected histograms (voting) |

Weighted datasets / uncompacted bagging keep the two-word (packed +
weight) layout; `quant_renew=false` pins the root scale and makes the
packed cores quantize bit-identically to the masked strategy.
"""


def esc(s):
    return str(s).replace("|", "\\|").replace("\n", " ")


def main():
    out = [HEADER]
    for p in PARAMS:
        doc = esc(p.get("doc", ""))
        if len(doc) > 400:
            doc = doc[:397] + "..."
        out.append("| `%s` | `%s` | %s | %s | %s |\n" % (
            p["name"], esc(p.get("default", "")),
            ", ".join("`%s`" % a for a in p.get("aliases", [])) or "—",
            ", ".join("`%s`" % c for c in p.get("check", [])) or "—",
            doc))
    out.append(FOOTER)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "Parameters.md")
    with open(path, "w") as fh:
        fh.writelines(out)
    print("wrote %s (%d parameters)" % (path, len(PARAMS)))


if __name__ == "__main__":
    main()
