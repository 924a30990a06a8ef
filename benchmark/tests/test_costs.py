"""Each costs/ function against a hand-worked case."""
import pytest

from benchmark import harness


def test_resnet50_is_24_5_gflop_an_image():
    cell = harness.Cell("resnet50.fit-b128")
    costs = cell.module("costs")
    # stem 118,013,952; stages 667,942,912 / 1,027,604,480 /
    # 1,464,598,528 / 808,976,384; classifier 2,048,000 (by hand)
    assert costs.forward_macs(cell.config) == 4_089_184_256
    assert costs.train_flops_per_sample(cell.config) == \
        6 * 4_089_184_256
    assert costs.train_flops_per_sample(cell.config) / 1e9 == \
        pytest.approx(24.5, abs=0.1)


def test_resnet_stem_alone():
    cell = harness.Cell("resnet50.fit-b128")
    costs = cell.module("costs")
    toy = dict(cell.config, num_layers=18, image=64, num_classes=10)
    # by hand: stem 32*32*64*3*49; four stages of two basic units
    stem = 32 * 32 * 64 * 147
    s1 = 4 * (16 * 16 * 64 * 64 * 9) + 16 * 16 * 64 * 64
    s2 = 8 * 8 * 128 * 64 * 9 + 3 * (8 * 8 * 128 * 128 * 9) \
        + 8 * 8 * 128 * 64
    s3 = 4 * 4 * 256 * 128 * 9 + 3 * (4 * 4 * 256 * 256 * 9) \
        + 4 * 4 * 256 * 128
    s4 = 2 * 2 * 512 * 256 * 9 + 3 * (2 * 2 * 512 * 512 * 9) \
        + 2 * 2 * 512 * 256
    assert costs.forward_macs(toy) == stem + s1 + s2 + s3 + s4 + 5120


def test_resnet_parameters_match_the_reference():
    cell = harness.Cell("resnet50.fit-b128")
    shapes = cell.module("reference").param_shapes(cell.config)
    total = sum(int(__import__("numpy").prod(s))
                for s in shapes.values())
    assert total == cell.config["parameters"]


def test_lm2048_parameters_and_flops():
    cell = harness.Cell("lm2048.serve-chat-backlog")
    costs = cell.module("costs")
    total, matmul = costs.parameters(cell.config)
    assert total == 939_790_336 == cell.config["parameters"]
    assert matmul == 16 * (4 * 2048 ** 2 + 2 * 2048 * 8192) \
        + 32768 * 2048 == 872_415_232
    shapes = cell.module("reference").param_shapes(cell.config)
    assert sum(int(__import__("numpy").prod(s))
               for s in shapes.values()) == total
    assert costs.forward_flops_per_token(cell.config, 0) == 2 * matmul
    assert costs.forward_flops_per_token(cell.config, 400) == \
        2 * matmul + 16 * 4 * 2048 * 400
    # 256 KiB of fp32 keys and values a token
    assert costs.decode_step_bytes(cell.config, [1]) \
        - costs.decode_step_bytes(cell.config, [0]) == 256 * 1024
    assert costs.decode_step_bytes(cell.config, []) == 4 * total
