"""Disassembler + stepper (the il/text + Stepper tooling role,
mixer/pkg/il/text/write.go + il/interpreter/stepper.go)."""
import subprocess
import sys

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.compiler.disasm import Stepper, disassemble
from istio_tpu.compiler.ruleset import Rule, compile_ruleset
from istio_tpu.expr.checker import AttributeDescriptorFinder
from istio_tpu.attribute.types import ValueType as V

FINDER = AttributeDescriptorFinder({
    "destination.service": V.STRING,
    "source.namespace": V.STRING,
    "request.path": V.STRING,
    "request.headers": V.STRING_MAP,
    "connection.mtls": V.BOOL,
    "key": V.STRING,
})

RULES = [
    Rule(name="svc-and-ns",
         match='destination.service == "reviews.default.svc" && '
               'source.namespace != "locked"'),
    Rule(name="path-or-mtls",
         match='request.path.startsWith("/admin") || connection.mtls',
         namespace="prod"),
    Rule(name="dyn-key", match='request.headers[key] == "x"'),   # fallback
    Rule(name="always", match=""),
]


def _prog():
    return compile_ruleset(RULES, FINDER, jit=False)


def test_disassemble_contents():
    text = disassemble(_prog())
    # header counts + layout line
    assert "4 rules" in text and "host-fallback" in text
    # atom table with (canonical) source text and tier annotations
    assert 'EQ($destination.service, "reviews.default.svc")' in text
    assert "[id-eq]" in text
    assert "[prefix-pack]" in text  # the startsWith byte predicate
    # per-rule DNFs in both polarities
    assert "M: " in text and "N: " in text
    assert "∧" in text and "∨" in text
    # fallback rules carry the reason, namespaces render
    assert "HOST FALLBACK" in text
    assert "ns=prod" in text
    # referenced attributes line
    assert "refs: " in text and "source.namespace" in text


def test_stepper_explains_verdicts():
    prog = _prog()
    stepper = Stepper(prog, FINDER)
    trace = stepper.explain(bag_from_mapping({
        "destination.service": "reviews.default.svc",
        "source.namespace": "prod",
        "request.path": "/admin/keys",
        "request.headers": {"cookie": "x"},
        "key": "cookie",
    }))
    assert "r0 svc-and-ns: MATCH via" in trace
    assert "r1 path-or-mtls: MATCH via" in trace
    assert "r3 always: MATCH" in trace
    # the dynamic-key rule went through the host oracle (headers[key]
    # resolves to headers["cookie"] == "x" → MATCH)
    assert "r2 dyn-key: MATCH (host oracle" in trace
    # atom values are shown with their (canonical) source
    assert "= True" in trace and "# EQ($destination.service" in trace


def test_stepper_explains_absence_and_error():
    prog = _prog()
    stepper = Stepper(prog, FINDER)
    trace = stepper.explain(bag_from_mapping({}), rule=0)
    assert "ERROR" in trace          # absent operands → inconclusive
    assert "lookup failed" in trace


def test_stepper_agrees_with_device():
    """The stepper's verdicts must equal the compiled program's."""
    import numpy as np
    from istio_tpu.compiler.layout import Tensorizer

    prog = _prog()
    stepper = Stepper(prog, FINDER)
    bags = [bag_from_mapping(d) for d in (
        {"destination.service": "reviews.default.svc",
         "source.namespace": "x"},
        {"request.path": "/admin/1"},
        {"connection.mtls": True},
        {"request.headers": {"k": "x"}, "key": "k"},
        {},
    )]
    batch = Tensorizer(prog.layout, prog.interner).tensorize(bags)
    matched, _, _ = prog(batch)
    matched = np.array(matched)
    for ridx in prog.host_fallback:
        for b, bag in enumerate(bags):
            matched[b, ridx] = prog.host_eval(ridx, bag)[0]
    for b, bag in enumerate(bags):
        trace = stepper.explain(bag)
        for ridx in range(prog.n_rules):
            name = prog.rules[ridx].name
            expects_match = bool(matched[b, ridx])
            line = next(ln for ln in trace.splitlines()
                        if ln.strip().startswith(f"r{ridx} {name}:"))
            assert (": MATCH" in line) == expects_match, \
                f"bag {b} rule {ridx}: {line}"


def test_rule_dump_cli(tmp_path):
    (tmp_path / "config.yaml").write_text("""
kind: handler
metadata: {name: denyall, namespace: istio-system}
spec: {adapter: denier, params: {}}
---
kind: instance
metadata: {name: nothing, namespace: istio-system}
spec: {template: checknothing, params: {}}
---
kind: rule
metadata: {name: deny-admin, namespace: istio-system}
spec:
  match: request.path.startsWith("/admin")
  actions: [{handler: denyall, instances: [nothing]}]
""")
    out = subprocess.run(
        [sys.executable, "-m", "istio_tpu.cmd", "rule-dump",
         "--config-store", str(tmp_path),
         "--explain", "request.path=/admin/x"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "deny-admin" in out.stdout
    assert "atoms:" in out.stdout
    assert "MATCH" in out.stdout
