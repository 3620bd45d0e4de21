"""Device time per named scope in one traced run of a cell.

    python3 onchip/trace_scopes.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``trace_cell.py`` does (its result line first), keeping
the trace, and compiles the text of each node the run serves (the same
programs, which the compile cache holds).  Each device operation in the
measured window is charged to the innermost ``jax.named_scope`` that its
HLO metadata names -- ``stem``, ``res<i>``, ``res<i>.down``, ``head`` --
or to ``other``; operations no node's text names are ``unmapped``.  The
last line is ``{"scopes": {scope: device seconds in the window}}``.
"""

import json
import re
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import trace_cell  # noqa: E402
import trace_reduce  # noqa: E402

SCOPE = re.compile(r"stem|head|res\d+(?:\.down)?")
INSTRUCTION = re.compile(r'\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"')


def scope_map(hlo_text: str) -> dict:
    """HLO instruction name -> the innermost scope its op_name names."""
    out = {}
    for line in hlo_text.splitlines():
        if m := INSTRUCTION.match(line):
            scopes = [p for p in m.group(2).split("/") if SCOPE.fullmatch(p)]
            out[m.group(1)] = scopes[-1] if scopes else "other"
    return out


def by_scope(events, names: dict) -> dict:
    """Device seconds in the measured window per scope."""
    (_, w0, w1) = [e for e in events.host
                   if e[0] == trace_reduce.WINDOW_SPAN][-1]
    out = defaultdict(float)
    for evs in events.device.values():
        for name, s, e in evs:
            if e > w0 and s < w1:
                out[names.get(name, "unmapped")] += (min(e, w1)
                                                     - max(s, w0)) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def capture_node_texts(texts: list):
    """Wrap ``harness.plan_signature`` so that it also appends the
    compiled text of each served node to `texts`."""
    import jax
    import numpy as np

    real = harness.plan_signature

    def signature(dep, tenant, words):
        pipe = harness._engine(dep, tenant).pipeline
        x = words[: dep.configuration(tenant).proper_batch_size]
        for node, fn in pipe.segment_fns:
            x = jax.device_put(np.asarray(x), pipe.device) \
                if node.on_device else np.asarray(x)
            jitted, args = getattr(fn, "func", fn), getattr(fn, "args", ())
            texts.append(jitted.lower(*args, x).compile().as_text())
            x = fn(x)
        return real(dep, tenant, words)

    harness.plan_signature = signature


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    texts: list = []
    capture_node_texts(texts)
    with tempfile.TemporaryDirectory() as tmp:
        keep = str(Path(tmp) / "run.xplane.pb")
        rc = trace_cell.main(argv + ["--keep", keep])
        if rc:
            return rc
        events = trace_reduce.load(keep)
    names = {}
    for text in texts:
        names.update(scope_map(text))
    print(json.dumps({"scopes": by_scope(events, names)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
