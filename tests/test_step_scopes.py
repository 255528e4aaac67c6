"""The train step's named scopes, for every optimizer branch on every
exchange path: the step is lowered (not compiled) at ``.reduced()`` size on
four host devices in a child process, and the ``op_name`` of each HLO
instruction is read with the scopes of the calls that lead to it.

Each branch names its oracle rounds (``lookahead`` for two-call branches,
``commit`` for all), ``update``, ``step_metrics`` and ``guard``; the staged
(bucketed) path names ``forward`` and ``backward``, the unstaged one marks
its backward ``transpose(`` inside the round.  Each exchange path names its
kernels, and ``exchange`` is one whole scope inside a round: no other scope
carries ``exchange/``, which the device-trace readers match.
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BRANCHES = {
    "extra_adam": ["--optimizer", "extra_adam"],
    "qgenx_de": ["--optimizer", "qgenx", "--method", "de"],
    "qgenx_optda": ["--optimizer", "qgenx", "--method", "optda"],
    "optimistic_adam": ["--optimizer", "optimistic_adam"],
    "adam": ["--optimizer", "adam"],
}
PATHS = {
    "bucketed": ["--compression", "int8", "--compress-mode", "two_phase",
                 "--use-pallas", "--num-buckets", "2",
                 "--overlap", "bucketed"],
    "two_phase": ["--compression", "int8", "--compress-mode", "two_phase"],
    # local steps: exchanges and the drift probe gated by lax.cond, and a
    # compressed re-centering of the iterates after the commit round
    "local_recenter": ["--compression", "int8", "--compress-mode",
                       "two_phase", "--sync-every", "2",
                       "--recenter-every", "2"],
    "gather": ["--compression", "int4", "--compress-mode", "gather"],
    "leafwise": ["--compression", "int8", "--compress-mode", "leafwise"],
    "ef21-topk": ["--compressor", "ef21-topk"],
    "none": [],
}
KERNELS = {
    "bucketed": {"noise", "quantize", "reduce", "dequantize"},
    "two_phase": {"noise", "quantize", "reduce", "dequantize"},
    "local_recenter": {"noise", "quantize", "reduce", "dequantize"},
    "gather": {"noise", "quantize", "reduce"},
    "leafwise": {"noise", "quantize", "reduce"},
    "ef21-topk": {"quantize", "dequantize"},
    "none": set(),
}
ROUNDS = ("lookahead", "commit")
CASES = [(b, p) for b in BRANCHES for p in PATHS]


def resolved_op_names(hlo: str) -> list:
    """Every instruction's op_name with the op_names of the ``call``s that
    lead to it (a called computation's names are relative to its call;
    loop and branch bodies carry their computation's own)."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        m = re.match(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(", line)
        if cur is None or not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        callees = re.findall(
            r"(?:to_apply|calls|body|condition|true_computation|"
            r"false_computation)=%?([\w.\-]+)", line)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        cur.append((m.group(1), name.group(1) if name else "", callees))
    out, seen = [], set()

    def walk(comp, prefix):
        if (comp, prefix) in seen:
            return
        seen.add((comp, prefix))
        for opcode, name, callees in comps.get(comp, []):
            full = prefix + name if name else prefix.rstrip("/")
            out.append(full)
            for c in callees:
                walk(c, full + "/" if opcode == "call" and name else prefix)

    walk(entry, "")
    return out


def _summary(names: list) -> dict:
    scopes, misplaced, transposed = set(), [], False
    for full in names:
        for loc in full.split(";"):
            parts = loc.split("/")
            scopes.update(parts[:-1])
            in_round = any(p in ROUNDS for p in parts)
            transposed |= in_round and "transpose(" in loc
            if "exchange" not in loc:
                continue
            at = [i for i, p in enumerate(parts) if "exchange" in p]
            if (len(at) != 1 or parts[at[0]] != "exchange"
                    or not any(p in ROUNDS for p in parts[:at[0]])):
                misplaced.append(loc)
    return {"scopes": sorted(scopes), "misplaced": misplaced[:5],
            "transposed": transposed}


def _lower_all(cases) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.registry import get_config
    from repro.core import faults
    from repro.launch import train

    mesh = Mesh(np.array(jax.devices()), ("data",))
    cfg = get_config("tinyllama-1.1b").reduced()
    batch = {k: jax.ShapeDtypeStruct((8, 16), jnp.int32)
             for k in ("tokens", "labels")}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    out = {}
    for branch, path in cases:
        args = train.build_parser().parse_args(
            BRANCHES[branch] + PATHS[path]
            + ["--guard", "--batch", "8", "--seq", "16"])
        run = train.build_run(args, cfg, mesh,
                              faults.parse_fault_spec_arg("", scope="train"))
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=run.state_sharding),
            jax.eval_shape(run.init_state, jax.random.PRNGKey(0)))
        hlo = run.step.lower(*state, batch, key).as_text("hlo",
                                                         debug_info=True)
        out[f"{branch}:{path}"] = _summary(resolved_op_names(hlo))
    return out


@pytest.fixture(scope="module")
def lowered():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("branch,path", CASES)
def test_step_names_its_stages_and_exchange_kernels(lowered, branch, path):
    got = lowered[f"{branch}:{path}"]
    scopes = set(got["scopes"])
    two_calls = branch in ("extra_adam", "qgenx_de")
    want = {"commit", "update", "guard"} | ({"lookahead"} if two_calls
                                            else set())
    if path != "none":
        want |= {"step_metrics", "exchange"}
    assert want <= scopes, want - scopes
    assert two_calls or "lookahead" not in scopes
    if path == "bucketed":
        assert {"forward", "backward", "bucket0", "bucket1"} <= scopes
    else:
        assert got["transposed"] and not {"forward", "backward"} & scopes
    assert KERNELS[path] <= scopes, KERNELS[path] - scopes
    assert not got["misplaced"], got["misplaced"]
    if path == "none":
        assert "exchange" not in scopes


if __name__ == "__main__":
    print(json.dumps(_lower_all(CASES)))
