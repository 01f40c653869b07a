"""A configuration's block is found through its own file: the shipped
configurations resolve to exactly the code they ran before (and to the same
seeded weights), and a stand-in cut in depth, with a program, weight tree,
reference and FLOP counts of its own, added to the shipped manifest as files
and entries (``conftest.py``), goes through the shipped ``train_steps``
runner."""

import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

from perfbench import device, weights
from perfbench import trace as pt
from perfbench.manifest import BLOCK_DEFAULTS, HERE, ROOT, Manifest

pytestmark = pytest.mark.tier1

STANDIN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "standin")
STANDIN_CONFIG = "gated-standin"
SHIPPED = [c["name"] for c in Manifest().doc["configs"]]

#: sha256 over every leaf (path, then float32 bytes) of ``make_params`` at
#: the configuration's rehearsal size for seed 2**31 + 17, read on the
#: parent of PR 26 (6af50a5), before the tree moved into ``weights/``.
DIGESTS = {
    "starcoder2-3b": (363776, "e0b71d76501fd5781c09dd6993e759527621e0dbca156ded2603f171f7aab4d3"),
    "gpt2-xl": (141312, "9bc367866dc6c946a8790b5f0378fa2e8fa757c89ecf637d893ef5394b20dc8a"),
}

#: what ``perfbench/README.md``'s table says each of a block's modules
#: must expose
EXPOSES = {"weights": ("param_specs",),
           "reference": ("forward_logits", "loss_and_grads"),
           "flops": ("train_flops_per_token",)}


@pytest.mark.parametrize(
    "man,name",
    [("shipped", n) for n in SHIPPED]
    + [("with_standin", n) for n in SHIPPED + [STANDIN_CONFIG]],
    indirect=["man"])
def test_shipped_configurations_run_the_default_block(man, name):
    """Every configuration of the manifest: a key of ``BLOCK_DEFAULTS`` its
    file leaves out resolves to the default block; a key it names resolves
    through ``Manifest`` to that module or attribute, which exposes what a
    runner will ask of it."""
    import chainermn_tpu.models as models

    cfg = man.config(name)
    if name in DIGESTS:  # the two that ran before there were keys
        assert set(BLOCK_DEFAULTS) & set(cfg) <= {"reference"}
        assert cfg.get("reference", "transformer_lm") == "transformer_lm"
    left_out = {k for k, v in BLOCK_DEFAULTS.items() if cfg.get(k, v) == v}
    if "program" in left_out:
        assert man.program(cfg) is models.TransformerLM
    if "loss" in left_out:
        assert man.loss(cfg) is models.lm_loss_chunked
    if "weights" in left_out:
        assert man.weights(cfg).__name__ == "perfbench.weights.transformer_lm"
    if "reference" in left_out:
        assert man.reference(cfg).__name__ == "perfbench.reference.transformer_lm"
    if "flops" in left_out:
        assert man.flops(cfg).__name__ == "perfbench.flops.transformer"
    for key in set(BLOCK_DEFAULTS) - left_out:
        got = getattr(man, key)(cfg)
        if key in EXPOSES:
            assert got.__file__ == os.path.join(man.data, key,
                                                f"{cfg[key]}.py")
            for attr in EXPOSES[key]:
                assert callable(getattr(got, attr)), (key, attr)
        else:
            assert callable(got)
            assert got.__name__ == cfg[key].partition(":")[2]


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_seeded_weights_did_not_move(name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    man = Manifest()
    cfg = man.config(name)
    specs = man.weights(cfg).param_specs(cfg["rehearse"]["model"])
    params = weights.make_params(specs, 2**31 + 17, jnp.float32)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf, np.float32).tobytes())
    assert (weights.n_params(specs), h.hexdigest()) == DIGESTS[name]


def test_only_the_blocks_own_modules_name_the_default_block():
    """Runners, checks, reducers and tools reach a block through the
    configuration's keys; its names appear in ``manifest.py`` as the
    documented defaults and nowhere else outside the block's own modules."""
    named = re.compile(r"transformer_lm|TransformerLM|flops\.transformer\b|"
                       r"flops import transformer")
    own = {"weights", "reference", "flops", "configs", "fixtures"}
    found = []
    for folder, _, files in os.walk(HERE):
        rel = os.path.relpath(folder, HERE)
        if rel.split(os.sep)[0] in own or "__pycache__" in rel:
            continue
        for f in files:
            if f.endswith((".py", ".json")) and named.search(
                    open(os.path.join(folder, f)).read()):
                found.append(os.path.join(rel, f))
    assert sorted(found) == ["./manifest.py"]


# ---------------------------------------------------------------- stand-in
def test_the_stand_in_shares_nothing_with_the_package():
    for folder, _, files in os.walk(STANDIN):
        for f in files:
            assert not os.path.exists(os.path.join(
                HERE, os.path.relpath(folder, STANDIN), f)), f


def test_standin_tree_is_its_programs(with_standin):
    import jax
    import jax.numpy as jnp

    man, cfg = with_standin, with_standin.config(STANDIN_CONFIG)
    m = cfg["model"]
    model = man.program(cfg)(dtype=jnp.float32, param_dtype=jnp.float32, **m)
    want = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    specs = man.weights(cfg).param_specs(m)
    got = jax.tree_util.tree_map(lambda s: s[0], specs,
                                 is_leaf=weights._is_spec)
    assert jax.tree_util.tree_map(lambda a: a.shape, want) == got
    # a block the default tree cannot express: no bias, three FFN kernels
    leaves = {jax.tree_util.keystr(p) for p, _ in
              jax.tree_util.tree_flatten_with_path(want)[0]}
    assert not any("bias" in k for k in leaves)
    assert {"['block_0']['gate']['kernel']", "['block_0']['up']['kernel']",
            "['block_0']['down']['kernel']", "['block_1']['g']['kernel']"} \
        <= leaves
    # and it names device work by the scope its file lists
    lowered = jax.jit(lambda p, t: model.apply({"params": p}, t)).lower(
        want, jax.ShapeDtypeStruct((1, 8), jnp.int32))
    assert cfg["scopes"] == ["mix.shift"]
    assert "mix.shift" in lowered.as_text(debug_info=True)


def test_a_cut_configuration_with_a_block_of_its_own_rehearses(with_standin):
    """Through ``python -m perfbench.run`` and the shipped ``train_steps``
    runner, from the shipped manifest with the stand-in's entries added:
    program, loss, weights, reference and optimizer twin are all found by
    the configuration's keys, and the check that decides ``correct``
    compares the two."""
    man, cfg = with_standin, with_standin.config(STANDIN_CONFIG)
    assert man.runner("train_steps").__name__ == "perfbench.runners.train_steps"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run(
        # niced: a compile beside the serving tests that assert latencies
        # (tests/serving_tests/test_serve_obs.py) must not starve them
        ["nice", "-n", "19", sys.executable, "-m", "perfbench.run",
         "--workload", "standin_train", "--seed", str(2**31 + 26),
         "--seconds", "0.3", "--rehearse",
         "--root", man.root, "--data", man.data],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    compared = {x["compared"]["number"]: x["compared"] for x in lines
                if "number" in x.get("compared", {})}
    assert set(compared) == {"loss_rel_gap", "grad_norm_rel_gap",
                             "param_change_rel_gap"}
    assert all(c["ok"] and c["value"] <= c["limit"] for c in compared.values())
    # each number beside its limit: last key of the result's line, and the
    # last lines of standard error
    assert list(last)[-1] == "compared" and last["compared"] == {
        k: {"value": c["value"], "limit": c["limit"]}
        for k, c in compared.items()}
    said = r.stderr.strip().splitlines()[-len(compared):]
    assert [x.split()[2] for x in said] == list(last["compared"])
    assert all(x.startswith("perfbench: compared ") and "(limit " in x
               for x in said)
    # the leaves compared are the stand-in tree's own
    detail = next(x["compared"]["detail"] for x in lines
                  if "detail" in x.get("compared", {}))
    assert detail["grad_leaf"].startswith(("block_", "embed", "norm_f",
                                           "lm_head"))


def _facts(man, cfg, **more):
    return dict({"manifest": man, "config": cfg,
                 "peaks": device.peaks("TPU v5 lite"),
                 "values": {"train_tokens_per_s": 1.0e6}}, **more)


def test_mfu_reads_the_configurations_own_flops(with_standin):
    man, cfg = with_standin, with_standin.config(STANDIN_CONFIG)
    own = man.flops(cfg)
    assert own.__name__ == "perfbench_ext.flops.gated_lm"
    per_token = own.train_flops_per_token(cfg["model"],
                                          cfg["train"]["seq_len"])
    # 2 attention + 2 shift mixers, 4 gated FFNs, the head, causal scores
    D, F, V, T = 64, 160, 512, 32
    assert per_token == 3 * (2 * (2 * 4 * D * D + 2 * 3 * D * D
                                  + 4 * 3 * D * F + D * V)
                             + 2 * 2 * 2 * D * (T + 1) / 2)
    [m] = [m for m in man.metrics_for("standin_train", "per_layer")
           if m["name"] == "standin_mfu"]
    spec = man.metric_file(m["name"])
    got = man.reducer(spec["reducer"]).reduce(_facts(man, cfg), spec["args"])
    assert got == pytest.approx(100 * 1.0e6 * per_token / 197e12)
    # the shipped dense count would have read another number (two FFN
    # kernels, four mixer kernels a layer)
    assert Manifest().flops({}).train_flops_per_token(cfg["model"], T) \
        != per_token


def test_kernel_roofline_finds_a_need_of_the_data_directory(with_standin):
    man, cfg = with_standin, with_standin.config(STANDIN_CONFIG)
    ops = [pt.Event("%fusion.1 = f32[8] fusion(f32[8] %x)", 0.0, 2.0),
           pt.Event("%fusion.2 = f32[8] fusion(f32[8] %x)", 4.0, 5.0)]
    t = pt.Trace({0: pt.DeviceTrace(ops, [pt.Event("jit_train(1)", 0.0, 5.0)])},
                 [pt.Event("pb:window", 0.0, 6.0)])
    spec = man.metric_file("standin_roofline")
    got = man.reducer(spec["reducer"]).reduce(_facts(man, cfg, trace=t),
                                              spec["args"])
    need = man.need("standin_kernel")(_facts(man, cfg), 2)
    assert need == {"flops": 2 * 2.0 * 32 * 64 * 160,
                    "bytes": 2 * 4.0 * (32 + 160) * 64}
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert got == pytest.approx(100 * least / 3.0)
    # the shipped needs are found from there too, and read what they read
    shipped = Manifest()
    full = shipped.config("starcoder2-3b")
    from perfbench.flops import flash_attention

    for name, fn in (("flash_head_dim_forward", flash_attention.forward),
                     ("flash_head_dim_backward", flash_attention.backward)):
        one = fn(1, 24, 2, 4096, 128)
        assert man.need(name)({"config": full}, 30) == \
            {k: 30 * v for k, v in one.items()}
