"""The traffic generator: determinism per seed, lengths, chunk widths."""
import json
import pathlib
import statistics

import cell
import traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"


def requests(mix, seed, per_client=12, vocab=64000):
    t = traffic.Traffic(traffic.load_mix(TRAFFIC / f"{mix}.json"), seed, vocab)
    return [t.request(c, i) for c in range(t.mix.clients) for i in range(per_client)]


def test_same_seed_same_requests():
    a = requests("chat", 2**31 + 3)
    b = requests("chat", 2**31 + 3)
    assert a == b
    c = requests("chat", 2**31 + 4)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_lengths_stay_in_the_mix_bounds():
    for mix in ("generate", "chat", "prefill"):
        m = traffic.load_mix(TRAFFIC / f"{mix}.json")
        for r in requests(mix, 7):
            assert m.prompt.min <= len(r.prompt) <= m.prompt.max
            assert all(0 <= t < 64000 for t in r.prompt)
            lo = 1 if r.index == 0 else m.output.min
            assert lo <= r.max_new_tokens <= m.output.max


def test_every_seed_serves_the_same_lengths():
    """Every seed gives each client the same stream of lengths."""
    def streams(seed):
        rs = requests("chat", seed, per_client=6)
        by_client = {}
        for r in rs:
            by_client.setdefault(r.client, []).append((len(r.prompt), r.max_new_tokens))
        return by_client

    a, b = streams(1), streams(2**31 + 9)
    assert a == b
    assert len(set(map(tuple, a.values()))) == len(a)  # the clients differ


def test_a_stream_prefix_spans_the_distribution():
    m = traffic.load_mix(TRAFFIC / "chat.json")
    rs = [r for r in requests("chat", 3, per_client=4) if r.index > 0]
    prompt_med = statistics.median(len(r.prompt) for r in rs)
    out_med = statistics.median(r.max_new_tokens for r in rs)
    assert abs(prompt_med / m.prompt.median - 1) < 0.15
    assert abs(out_med / m.output.median - 1) < 0.15


def test_first_requests_take_the_residual_life():
    rs = [r for r in requests("generate", 5, per_client=1)]
    m = traffic.load_mix(TRAFFIC / "generate.json")
    firsts = sorted(r.max_new_tokens for r in rs)
    assert firsts[0] < m.output.min  # some clients are near their end
    assert len(set(firsts)) == len(firsts)


def test_chunk_widths():
    g = traffic.Traffic(traffic.load_mix(TRAFFIC / "generate.json"), 0, 64000)
    assert g.chunk_widths(16, 64) == [16, 32, 48, 64]
    c = traffic.Traffic(traffic.load_mix(TRAFFIC / "chat.json"), 0, 64000)
    assert c.chunk_widths(16, 256) == list(range(16, 257, 16))
    p = traffic.Traffic(traffic.load_mix(TRAFFIC / "prefill.json"), 0, 64000)
    assert p.chunk_widths(16, 64) == [16, 32, 48, 64]


def test_every_cell_s_longest_request_fits_a_slot():
    bench = json.loads((TRAFFIC.parents[2] / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = cell.load_cell(w["name"])
        m = traffic.load_mix(c.mix_path)
        assert m.prompt.max + m.output.max <= c.config["engine"]["max_seq_len"], w["name"]
