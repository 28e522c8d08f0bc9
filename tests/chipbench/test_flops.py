"""The FLOP and byte functions against hand counts."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import load_by_path  # noqa: E402


def test_bert_train_flops_by_hand():
    fam = load_by_path("families", "bert_ae")
    s = dict(num_hidden_layers=12, hidden_size=1024, ffn_mult=4, seq=512,
             batch=32)
    # per layer and token: 12 * hidden^2 multiply-adds in the six dense
    # matmuls, 2 * seq * hidden in attention; times 2, times 3
    per_token = 12 * (12 * 1024 ** 2 + 2 * 512 * 1024) + 1024
    assert fam.train_flops_per_sample(s) == 3 * 2 * 512 * per_token
    # 151 M weights * 6 * tokens, plus attention
    assert abs(fam.train_flops_per_sample(s) / 512 - 6 * 151e6) < 0.1e9


def test_flash_flops_and_bytes_by_hand():
    fam = load_by_path("families", "bert_ae")
    s = dict(num_hidden_layers=12, hidden_size=1024, seq=8192, batch=2)
    flops, nbytes = fam.flash_step_flops_and_bytes(s)
    assert flops == 12 * 2 * 8192 ** 2 * 1024 * 12
    assert nbytes == 12 * (2 * 2 * 8192 * 1024) * 12


def test_inception_flops_match_the_published_count():
    fam = load_by_path("families", "inception_v3_ae")
    flops = fam.train_flops_per_sample(dict(image_size=299, num_classes=1000))
    # Inception-v3 is 5.7 G multiply-adds forward at 299x299
    assert 5.5e9 < flops / 6 < 5.9e9
    ref = load_by_path("references", "inception_v3_ae")
    arch = ref.shapes(299, 1000)
    assert len(arch.convs) == 94 and arch.fc == (2048, 1000)
    assert arch.convs[0] == (32, 3, 3, 3, 149, 149, True)
