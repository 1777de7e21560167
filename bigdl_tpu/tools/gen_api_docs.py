"""Generate the API reference from the package's docstrings (the role
of the reference's mkdocs APIGuide tree — one command regenerates the
whole index), and GATE completeness: every public symbol must carry a
docstring (--check; wired into the test suite).

    python -m bigdl_tpu.tools.gen_api_docs           # docs/api.md +
                                                     # docs/api/<family>.md
    python -m bigdl_tpu.tools.gen_api_docs --check   # exit 1 on any
                                                     # undocumented symbol
"""
from __future__ import annotations

import importlib
import inspect
import os
import sys

# family -> modules (one navigable page per family, APIGuide-style)
FAMILIES = {
    "nn": ["bigdl_tpu.nn", "bigdl_tpu.nn.attention", "bigdl_tpu.nn.moe",
           "bigdl_tpu.nn.sparse", "bigdl_tpu.nn.quantized"],
    "dataset": ["bigdl_tpu.dataset", "bigdl_tpu.dataset.device_dataset",
                "bigdl_tpu.dataset.fetch"],
    "datapipe": ["bigdl_tpu.datapipe", "bigdl_tpu.datapipe.readers",
                 "bigdl_tpu.datapipe.shuffle",
                 "bigdl_tpu.datapipe.packing",
                 "bigdl_tpu.datapipe.stage",
                 "bigdl_tpu.datapipe.pipeline"],
    "optim": ["bigdl_tpu.optim"],
    "serving": ["bigdl_tpu.serving"],
    "generation": ["bigdl_tpu.generation", "bigdl_tpu.generation.kv_cache",
                   "bigdl_tpu.generation.engine",
                   "bigdl_tpu.generation.loop",
                   "bigdl_tpu.generation.stream",
                   "bigdl_tpu.generation.sampling"],
    "fleet": ["bigdl_tpu.fleet", "bigdl_tpu.fleet.prefix",
              "bigdl_tpu.fleet.speculative", "bigdl_tpu.fleet.router",
              "bigdl_tpu.fleet.replica", "bigdl_tpu.fleet.soak",
              "bigdl_tpu.fleet.control", "bigdl_tpu.fleet.admission",
              "bigdl_tpu.fleet.deploy"],
    "kernels": ["bigdl_tpu.kernels", "bigdl_tpu.kernels.config",
                "bigdl_tpu.kernels.dispatch",
                "bigdl_tpu.kernels.flash_attention",
                "bigdl_tpu.kernels.ragged_decode",
                "bigdl_tpu.kernels.int8_gemm",
                "bigdl_tpu.kernels.common"],
    "autotune": ["bigdl_tpu.autotune", "bigdl_tpu.autotune.space",
                 "bigdl_tpu.autotune.defaults",
                 "bigdl_tpu.autotune.prune",
                 "bigdl_tpu.autotune.measure",
                 "bigdl_tpu.autotune.config"],
    "analysis": ["bigdl_tpu.analysis", "bigdl_tpu.analysis.shapecheck",
                 "bigdl_tpu.analysis.lint", "bigdl_tpu.analysis.concur",
                 "bigdl_tpu.analysis.hlo", "bigdl_tpu.analysis.checks",
                 "bigdl_tpu.analysis.programs"],
    "telemetry": ["bigdl_tpu.telemetry", "bigdl_tpu.telemetry.tracer",
                  "bigdl_tpu.telemetry.metrics",
                  "bigdl_tpu.telemetry.export",
                  "bigdl_tpu.telemetry.programs",
                  "bigdl_tpu.telemetry.flight",
                  "bigdl_tpu.telemetry.agg",
                  "bigdl_tpu.telemetry.slo"],
    "tools": ["bigdl_tpu.tools.regress", "bigdl_tpu.tools.deploy"],
    "faults": ["bigdl_tpu.faults", "bigdl_tpu.faults.retry"],
    "elastic": ["bigdl_tpu.elastic", "bigdl_tpu.elastic.checkpoint",
                "bigdl_tpu.elastic.resume", "bigdl_tpu.elastic.preempt",
                "bigdl_tpu.elastic.capability"],
    "parallel": ["bigdl_tpu.parallel", "bigdl_tpu.parallel.zero",
                 "bigdl_tpu.parallel.sequence",
                 "bigdl_tpu.parallel.ring_attention",
                 "bigdl_tpu.parallel.ulysses"],
    "precision": ["bigdl_tpu.precision", "bigdl_tpu.precision.policy",
                  "bigdl_tpu.precision.scaler",
                  "bigdl_tpu.precision.calibrate",
                  "bigdl_tpu.precision.gate"],
    "models": ["bigdl_tpu.models"],
    "interop": ["bigdl_tpu.utils.serialization",
                "bigdl_tpu.utils.tf_loader", "bigdl_tpu.utils.tf_fusion",
                "bigdl_tpu.utils.caffe", "bigdl_tpu.utils.torch_file"],
    "runtime": ["bigdl_tpu.utils.engine", "bigdl_tpu.ml",
                "bigdl_tpu.visualization"],
}
MODULES = [m for mods in FAMILIES.values() for m in mods]


def _first_line(doc) -> str:
    if not doc:
        return ""
    return doc.strip().splitlines()[0].rstrip()


def _public_members(mod):
    names = getattr(mod, "__all__", None) or [
        n for n in vars(mod) if not n.startswith("_")]
    out = []
    for n in sorted(set(names)):
        obj = getattr(mod, n, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        home = getattr(obj, "__module__", "")
        if not home.startswith("bigdl_tpu"):
            continue
        kind = "class" if inspect.isclass(obj) else "def"
        try:
            sig = str(inspect.signature(obj))
        except (TypeError, ValueError):
            sig = "(...)"
        if len(sig) > 70:
            sig = sig[:67] + "..."
        out.append((kind, n, sig, _first_line(inspect.getdoc(obj))))
    return out


def _module_section(name: str, heading: str = "##") -> list:
    lines = []
    mod = importlib.import_module(name)
    lines.append(f"{heading} `{name}`")
    head = _first_line(inspect.getdoc(mod))
    if head:
        lines.append(f"\n{head}\n")
    for kind, n, sig, doc in _public_members(mod):
        entry = f"- **`{n}{sig}`**"
        if doc:
            entry += f" — {doc}"
        lines.append(entry)
    lines.append("")
    return lines


def generate() -> str:
    lines = ["# API index",
             "",
             "Generated from docstrings by "
             "`python -m bigdl_tpu.tools.gen_api_docs` — regenerate "
             "after adding public API. Per-family pages: "
             + ", ".join(f"[{f}](api/{f}.md)" for f in FAMILIES), ""]
    for name in MODULES:
        lines.extend(_module_section(name))
    return "\n".join(lines) + "\n"


def generate_family(family: str) -> str:
    lines = [f"# `{family}` API",
             "",
             "Generated from docstrings by "
             "`python -m bigdl_tpu.tools.gen_api_docs`. "
             "[Back to index](../api.md).", ""]
    for name in FAMILIES[family]:
        lines.extend(_module_section(name))
    return "\n".join(lines) + "\n"


def undocumented() -> list:
    """Every public top-level symbol (class or function reachable from
    the MODULES surface) lacking a docstring — the completeness gate.
    Methods inherit docs through ``inspect.getdoc``'s base-class walk,
    so the gate anchors on the symbols the API pages index."""
    missing = []
    for name in MODULES:
        mod = importlib.import_module(name)
        for kind, n, sig, doc in _public_members(mod):
            if not inspect.getdoc(getattr(mod, n)):
                missing.append(f"{name}.{n}")
    return sorted(set(missing))


def main(argv=None):
    args = list(argv if argv is not None else sys.argv[1:])
    if args and args[0] == "--check":
        missing = undocumented()
        if missing:
            print("undocumented public symbols:")
            for m in missing:
                print(f"  {m}")
            raise SystemExit(1)
        print(f"all public symbols documented "
              f"({len(MODULES)} modules)")
        return
    out = args[0] if args else "docs/api.md"
    text = generate()
    with open(out, "w") as f:
        f.write(text)
    print(f"wrote {out} ({text.count(chr(10))} lines)")
    fam_dir = os.path.join(os.path.dirname(os.path.abspath(out)), "api")
    os.makedirs(fam_dir, exist_ok=True)
    for fam in FAMILIES:
        fp = os.path.join(fam_dir, fam + ".md")
        with open(fp, "w") as f:
            f.write(generate_family(fam))
        print(f"wrote {fp}")


if __name__ == "__main__":
    main()
