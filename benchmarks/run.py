"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [section ...]

Prints ``name,us_per_call,derived`` CSV. CPU wall numbers are measured here;
'tpu_us'/'speedup_model' values are derived from measured iteration counts ×
the v5e roofline traffic model (benchmarks/common.py) — both are labeled.
Neither is a chip measurement. A section that raises prints an ERROR row
and the run exits non-zero.
"""

import sys

from .common import emit


SECTIONS = {}


def _register():
    from . import engine_bench as eb
    from . import operator_bench as ob
    from . import paged_attn_bench as pab
    from . import paged_bench as pb
    from . import sp_engine_bench as spb
    from . import spec_bench as spcb
    from . import system_bench as sb
    SECTIONS.update({
        "engine": eb.bench_engine,
        "paged": pb.bench_paged,
        "paged_attn": pab.bench_paged_attn,
        "sp_engine": spb.bench_sp_engine,
        "spec": spcb.bench_spec,
        "table1": ob.bench_table1_pass_counts,
        "table6": ob.bench_table6_synthetic_latency,
        "table7": ob.bench_table7_per_layer_speedup,
        "table8": ob.bench_table8_distribution_sensitivity,
        "table9": ob.bench_table9_preidx_ablation,
        "table10": ob.bench_phase_breakdown,
        "fig3": sb.bench_fig3_temporal_overlap,
        "fig11": sb.bench_fig11_e2e_decode,
        "kernels": sb.bench_kernels,
    })
    from . import roofline
    SECTIONS["roofline_serving"] = roofline.bench_roofline_serving
    # reads results/dryrun/ (git-ignored): fails loudly when it is missing
    SECTIONS["roofline"] = roofline.bench_roofline


def main() -> int:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    _register()
    names = sys.argv[1:] or list(SECTIONS)
    rows = []
    failed = []
    for name in names:
        try:
            rows.extend(SECTIONS[name]())
        except Exception as e:  # noqa: BLE001 — report every section first
            rows.append((f"{name}/ERROR", "", repr(e)[:120]))
            failed.append(name)
    emit(rows)
    if failed:
        print(f"failed sections: {failed}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
