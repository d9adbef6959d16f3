"""Every part of the sequence models' serving step runs under exactly one
of a fixed vocabulary of call names (``jax.named_call``), which is how a
trace reduction prices the step by part (``benchmark/readers/
scope_ms_per_tick.py``): read here from the lowered module's ``op_name``
metadata at the model tests' tiny configurations, with no chip."""

import re

import jax
import pytest

from anomod.models import hybrid_ssm_moe as hm
from anomod.models import latent_moe as lm
from anomod.models import seqcommon
from anomod.ops import gqa_attention as ga
from anomod.ops import routed_experts as rx
from anomod.ops import ssm_scan as ss
from anomod.serve import seqplane as sp
from test_hybrid_ssm_moe import TINY as N3S
from test_latent_moe import TINY as K2
from test_swa_moe import TINY as LXS2

SHARED = {seqcommon.PROJ_SCOPE, rx.ROUTE_SCOPE, rx.ROUNDS_SCOPE,
          seqcommon.MLP_SCOPE, seqcommon.HEAD_SCOPE}
#: the fixed vocabulary: ten call names (the eleventh name a reduction
#: reads, ``ragged-dot-none``, is the compiler's, for the expanded
#: grouped matmuls)
SCOPES = SHARED | {hm.CONV_SCOPE, lm.ATTENTION_SCOPE, ga.SCOPE, ga.SWA_SCOPE,
                   ss.SCOPE}
MODELS = {"k2": (K2, SHARED | {lm.ATTENTION_SCOPE}),
          "n3s": (N3S, SHARED | {hm.CONV_SCOPE, ss.SCOPE, ga.SCOPE}),
          "lxs2": (LXS2, SHARED | {ga.SCOPE, ga.SWA_SCOPE})}
HEAVY = {"dot", "convolution", "ragged-dot", "gather", "scatter", "sort",
         "custom-call"}
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "call",
            "while", "conditional"}
#: what may stay outside every scope inside a loop's body (K2's layers run
#: under ``lax.scan``): the norms between parts, the residual adds and the
#: scan's own slicing of its stacked weights
NORMS_AND_ADDS = {"mul", "add", "div", "rsqrt", "reduce_sum", "integer_pow",
                  "square", "convert_element_type", "broadcast_in_dim",
                  "dynamic_slice", "dynamic_update_slice", "squeeze", "lt",
                  "select_n", "closed_call"}


def instructions(hlo: str):
    """``(opcode, full op_name)`` of every instruction reachable from the
    entry computation through calls, loops and branches.  An instruction
    of a called computation carries a name relative to its call (XLA's
    inliner joins them, which is what a device trace shows): joined here
    the same way."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .+? ([a-z\-]+)\(", line)
        if m and cur is not None:
            name = re.search(r'op_name="([^"]*)"', line)
            callees = re.findall(
                r"(?:to_apply|body|condition|true_computation|"
                r"false_computation)=%?([\w.\-]+)", line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                callees += [b.strip().lstrip("%")
                            for b in branches.group(1).split(",")]
            cur.append((m.group(1), name.group(1) if name else "", callees))

    def walk(comp, prefix):
        for opcode, name, callees in comps[comp]:
            full = prefix + name
            yield opcode, full
            if opcode in ("call", "while", "conditional"):
                inner = full + "/" if opcode == "call" else prefix
                for callee in callees:
                    yield from walk(callee, inner)

    return list(walk(entry, ""))


def scopes_of(op_name: str) -> set:
    """The scopes an ``op_name`` carries (``a;b`` where the lowering merged
    two ops into one)."""
    return SCOPES.intersection(re.split("[/;]", op_name))


@pytest.mark.parametrize("model", sorted(MODELS))
def test_every_part_of_the_step_runs_under_exactly_one_scope(model):
    from jax._src.lib import xla_client
    spec, expected = MODELS[model]
    m = sp.MODELS.get(spec.get("model_type"), sp.LatentMoE)(spec)
    lowered = jax.jit(m.step).lower(
        jax.eval_shape(lambda: m.init_params(0)),
        jax.eval_shape(lambda: m.init_state(6)),
        m.empty_plan(m.caps(128, 12), 6))
    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    ops = instructions(lowered.compiler_ir(dialect="hlo").as_hlo_module()
                       .to_string(options))
    assert len(ops) > 1000
    seen, outside = set(), []
    for opcode, name in ops:
        found = scopes_of(name)
        assert len(found) <= 1, (opcode, name)
        seen.update(found)
        if not found and opcode not in PLUMBING:
            outside.append((opcode, name))
    assert seen == expected
    # outside every scope: the embedding's row gather and nothing else
    # that is heavy; inside a loop's body only norms and residual adds
    heavy = [(o, n) for o, n in outside if o in HEAVY]
    assert [o for o, _ in heavy] == ["gather"], heavy
    assert "/while/" not in heavy[0][1]
    looped = {n.rstrip("/").rsplit("/", 1)[-1] for _, n in outside
              if "/while/" in n}
    assert looped <= NORMS_AND_ADDS, looped - NORMS_AND_ADDS
    assert bool(looped) == (model == "k2")
    # every loop is some part's own, or the layers' scan (K2's)
    loops = [n for o, n in ops if o == "while" and not scopes_of(n)]
    assert len(loops) == (2 if model == "k2" else 0), loops


def test_the_walk_joins_a_called_computations_names_to_its_call():
    hlo = """
%inner.1 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %d = f32[4]{0} dot(%a, %a), metadata={op_name="while/body/dot_general"}
}

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %g = f32[4]{0} gather(%x, %x), metadata={op_name="jit(step)/gather"}
  ROOT %c = f32[4]{0} call(%g), to_apply=%inner.1, metadata={op_name="jit(s)/anomod_seq_gqa/jit(f)"}
}
"""
    call = "jit(s)/anomod_seq_gqa/jit(f)"
    assert instructions(hlo) == [
        ("parameter", ""), ("gather", "jit(step)/gather"), ("call", call),
        ("parameter", call + "/"),
        ("dot", call + "/while/body/dot_general")]
    assert scopes_of("jit(step)/anomod_seq_gqa/jit(f)/while/body/dot") \
        == {ga.SCOPE}
    assert scopes_of("jit(step)/anomod_seq_conv/squeeze;jit(step)/"
                     "anomod_seq_conv/broadcast_in_dim") == {hm.CONV_SCOPE}
    assert scopes_of("jit(step)/while/body/anomod_seq_proj/"
                     "anomod_seq_mlp/dot") == {seqcommon.PROJ_SCOPE,
                                               seqcommon.MLP_SCOPE}


LOWER = """
import hashlib, importlib, sys
import jax
from anomod.serve import seqplane as sp
for name in ("test_latent_moe", "test_hybrid_ssm_moe", "test_swa_moe"):
    spec = importlib.import_module(name).TINY
    m = sp.MODELS.get(spec.get("model_type"), sp.LatentMoE)(spec)
    text = jax.jit(m.step).lower(
        jax.eval_shape(lambda: m.init_params(0)),
        jax.eval_shape(lambda: m.init_state(6)),
        m.empty_plan(m.caps(128, 12), 6)).as_text()
    print(name, hashlib.sha1(text.encode()).hexdigest())
"""


def test_the_lowered_step_does_not_change_with_the_process_s_string_hashing():
    """PR 38 found the window-and-full step lowered in one of two orders
    (a loop over a ``set`` of layer kinds): two compile-cache keys for
    one program, and a cold ~40 s compile on the runs that drew the other
    (the ledger's ``setup_s`` spread of 0.21 in ``lxs2-fleet-overload``)."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    seen = set()
    for seed in ("1", "3"):            # these two gave the two orders
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join([os.path.dirname(here), here]))
        out = subprocess.run([sys.executable, "-c", LOWER], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        seen.add(out.stdout)
    assert len(seen) == 1 and next(iter(seen)).count("\n") == 3
