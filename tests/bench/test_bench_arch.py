"""Architecture modules (``bench/archs``).

The dense decoder gives the weight layout, weights, program mapping and
work counts recorded in ``data/dense_golden.json``, which were taken
from the dense code before it moved into ``bench/archs/dense_decoder.py``.
A sparse-expert stack with windowed layers (``data/tiny_moe.py`` with
``data/tiny-moe.json``) goes through the harness's own loaders and
work counts, with the loaders pointed at ``data`` and no harness file
edited."""

import dataclasses
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

import bench.archs
from bench import model
from bench import trace as tr
from bench.archs.dense_decoder import Dims
from bench.metrics import paged_decode_roofline
from bench.work import Step, decode_attention, head_flops, step_flops

DATA = Path(__file__).resolve().parent / "data"
GOLDEN = json.loads((DATA / "dense_golden.json").read_text())
DENSE = sorted(GOLDEN["configs"])
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def leaves(tree, path=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from leaves(v, f"{path}/{k}")
    else:
        yield path, tree


@pytest.mark.parametrize("name", DENSE)
def test_dense_weight_layout_is_unchanged(name):
    conf = model.load_config(name)
    dims = model.dims_of(conf)
    dt = conf["model"]["torch_dtype"]
    made = dict(leaves(jax.eval_shape(
        lambda: model.make_weights(dims, 0, dt))))
    rows = []
    for path, shape in leaves(dims.weight_shapes()):
        assert tuple(made[path].shape) == tuple(shape)
        rows.append([path, list(shape), str(made[path].dtype),
                     None if "norm" in path else dims.scale(path)])
    assert len(made) == len(rows)
    assert rows == GOLDEN["configs"][name]["leaves"]


@pytest.mark.parametrize("tied", [True, False])
def test_dense_weights_are_bit_identical(tied):
    dims = Dims(layers=2, d=32, heads=4, kv_heads=2, head_dim=8, ff=64,
                vocab=64, tied=tied, rope_theta=1e4, eps=1e-5)
    h = hashlib.sha256()
    w = model.make_weights(dims, 5, "float32")
    for path, a in sorted(leaves(w), key=lambda pa: pa[0]):
        h.update(path.encode())
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == GOLDEN["checksums"]["tied" if tied else
                                                 "untied"]


@pytest.mark.parametrize("name", DENSE)
def test_dense_program_config_is_unchanged(name):
    got = model.program_config(model.load_config(name))
    assert dataclasses.asdict(got) == GOLDEN["configs"][name][
        "program_config"]


@pytest.mark.parametrize("name", DENSE)
def test_dense_work_counts_are_unchanged(name):
    dims = model.dims_of(model.load_config(name))
    want = GOLDEN["configs"][name]
    steps = [Step([tuple(c) for c in chunks], lens)
             for chunks, lens in GOLDEN["steps"]]
    assert [step_flops(dims, st) for st in steps] == want["step_flops"]
    for layer in range(dims.layers):
        assert [list(decode_attention(dims, layer, lens))
                for lens in GOLDEN["lens"]] == want["decode_attention"]
    assert [head_flops(dims, n) for n in GOLDEN["emitted"]] == \
        want["head_flops"]


def test_a_configuration_names_its_architecture():
    conf = model.load_config(DENSE[0])
    del conf["reference"]
    with pytest.raises(KeyError):
        model.dims_of(conf)


@pytest.fixture
def moe(monkeypatch):
    """The fixture configuration, read by the harness's loaders pointed
    at ``data``: where a new configuration's files would go."""
    monkeypatch.setattr(bench.archs, "__path__",
                        [*bench.archs.__path__, str(DATA)])
    monkeypatch.setattr(model, "CONFIG_DIR", DATA)
    return model.load_config("tiny-moe")


def test_moe_sizes_and_weights(moe):
    dims = model.dims_of(moe)
    assert type(dims).__module__ == "bench.archs.tiny_moe"
    assert dims.windows == (16, 16, 16, 0) * 2
    w = model.make_weights(dims, 2 ** 33 + 3, "float32")
    ffn = w["stack"]["p0"]["ffn"]
    assert ffn["router"].shape == (8, 64, 8)
    assert ffn["wg"].shape == ffn["wu"].shape == (8, 8, 64, 32)
    assert ffn["wd"].shape == (8, 8, 32, 64)
    assert w["embed"]["head"].shape == (64, 256)
    assert float(np.std(ffn["wd"])) == pytest.approx(32 ** -0.5, rel=0.05)
    assert float(np.std(ffn["router"])) == pytest.approx(64 ** -0.5,
                                                         rel=0.05)
    assert float(np.mean(w["stack"]["p0"]["norm1"])) == pytest.approx(
        1.0, abs=0.02)


def test_moe_program_mapping(moe):
    with pytest.raises(ValueError, match="windowed layers of 16"):
        model.program_config(moe)
    cfg = model.program_config(dict(moe, max_context=16))
    assert (cfg.family, cfg.n_layers, cfg.d_model, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim) == ("moe", 8, 64, 4, 2, 16)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_ff) == (8, 2, 32)
    assert (cfg.vocab_size, cfg.tie_embeddings, cfg.sliding_window,
            cfg.dtype) == (256, False, 0, "float32")


def test_moe_counts_active_experts_only(moe):
    dims = model.dims_of(moe)
    # projections 64 * 16 * (8 + 4), router 64 * 8, and 2 of the 8
    # experts at 3 * 64 * 32 each
    active = 12288 + 512 + 2 * 3 * 64 * 32
    assert [dims.layer_matmul_params(i) for i in range(8)] == [active] * 8
    # one token at position 0 reads one key in every layer
    assert step_flops(dims, Step([(0, 1)], [])) == 8 * (
        2 * active + 4 * 4 * 16 * 1)


@pytest.mark.parametrize("pos,windowed", [(0, 1), (5, 6), (15, 16),
                                          (16, 16), (40, 16)])
def test_moe_windowed_layers_read_at_most_the_window(moe, pos, windowed):
    dims = model.dims_of(moe)
    assert dims.keys_read(0, pos) == windowed
    assert dims.keys_read(3, pos) == pos + 1
    assert list(dims.keys_read(6, np.array([pos, 99]))) == [windowed, 16]


def test_moe_step_and_roofline_sum_over_layers(moe):
    dims = model.dims_of(moe)
    # positions 30..33 and a decode lane at 40: 16 keys each in the six
    # windowed layers, 31 + 32 + 33 + 34 + 41 = 171 in the two full ones
    assert step_flops(dims, Step([(30, 4)], [41])) == (
        8 * 2 * 25088 * 5 + 4 * 4 * 16 * (6 * 5 * 16 + 2 * 171))
    assert decode_attention(dims, 0, [41]) == (256.0 * 16,
                                               128.0 * 16 + 256)
    assert decode_attention(dims, 7, [41]) == (256.0 * 41,
                                               128.0 * 41 + 256)
    # memory-bound least time (128 n + 256) / 10: 230.4 in a windowed
    # layer, 550.4 in a full one; 2483.2 over the eight, against 4966.4
    # in the kernel
    kernel = ("%_decode_paged_impl.63 = (f32[4,5,32,3,64]{4,3,2,1,0}, "
              "f32[4,5,32,3,1]{4,3,2,1,0}) custom-call(s32[4,32]{1,0} %x)")
    t = tr.from_events((0.0, 1e4), {"/device:TPU:0": {
        "ops": [(kernel, 0.0, 4966.4)], "modules": []}}, [])
    run = NS(trace=t, steps=[Step([], [41])], dims=dims, peaks=PEAKS)
    assert paged_decode_roofline.read(run) == pytest.approx(50.0)
