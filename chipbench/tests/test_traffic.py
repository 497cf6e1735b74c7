"""The traffic generator: same seed, same requests; every seed, the same
sizes in the same order; a mix is found by its file's name."""
import json
import os
import shutil

import numpy as np

from chipbench import cells as C
from chipbench.traffic import ClosedLoop, cycle_sizes


def _draw(mix, seed, n=60):
    gen = ClosedLoop(mix, 32064, seed)
    return [gen.next_request(i % gen.clients) for i in range(n)]


def test_same_seed_same_requests():
    mix = C.load_traffic("docqa-fit-1k")
    a, b = _draw(mix, 2 ** 31 + 11), _draw(mix, 2 ** 31 + 11)
    assert [(r.max_new, r.prompt.tolist()) for r in a] == [
        (r.max_new, r.prompt.tolist()) for r in b]
    c = _draw(mix, 12)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


def test_every_seed_offers_the_same_sizes():
    mix = C.load_traffic("docqa-fit-1k")
    n = mix["clients"]
    for seed in (1, 2, 3 * 10 ** 9):
        sizes = [(len(r.prompt), r.max_new) for r in _draw(mix, seed, 3 * n)]
        assert sizes == cycle_sizes(mix) * 3
    prompts = [p for p, _ in cycle_sizes(mix)]
    outs = [o for _, o in cycle_sizes(mix)]
    assert set(prompts) == {512, 768}
    assert min(outs) == 64 and max(outs) == 128
    assert all(2 <= t < 32064 for r in _draw(mix, 5) for t in r.prompt)
    assert abs(np.mean(outs) - 96) < 0.5


def test_outputs_spread_over_the_cycle_for_any_client_count():
    for clients in range(2, 15):
        mix = {"clients": clients, "prompt_lengths": [512],
               "output_range": [64, 128]}
        outs = [o for _, o in cycle_sizes(mix)]
        assert sorted(outs) == sorted(set(outs)) and len(outs) == clients
        assert min(outs) == 64 and max(outs) == 128


def test_a_new_mix_is_found_by_name(tmp_path):
    d = tmp_path / "traffic"
    shutil.copytree(C.TRAFFIC_DIR, d)
    mix = dict(C.load_traffic("docqa-fit-1k"), clients=3)
    (d / "my-new-mix.json").write_text(json.dumps(mix))
    bench = C.load_benchmark()
    bench["workloads"] = [dict(bench["workloads"][0], name="x.new",
                               traffic="my-new-mix")]
    cell = C.find_cell("x.new", bench, traffic_dir=str(d))
    assert cell.geometry.max_batch == 3
    assert os.path.basename(str(d)) == "traffic"
