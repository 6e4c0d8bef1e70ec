"""``round.trainer_chunks`` (PR 58): in the manifest, read by the
counter reader from ``trainer.round_chunks``; 2.0 from a window of
one-chunk rounds of two workers, nothing from a program that books no
such counter (a parent), the plan's size where the trainer cut its
round at the declared link."""

from benchmark import manifest, readers

NAME = "round.trainer_chunks"


def _ctx(snaps, rounds=4):
    return readers.Context(
        cell="gpt2s-hips-bsc", chips=1, peaks=None, rounds=rounds,
        snaps=snaps, trace=None, tokens_traced=0, reference=None, cfg={},
        seq_len=1024)


def test_the_metric_is_in_the_manifest_as_its_file_says():
    man = manifest.load()
    entry = {m["name"]: m for m in man["per_layer"]}[NAME]
    spec = manifest.layer_metric_spec(NAME)
    assert all(entry[k] == spec[k]
               for k in ("name", "unit", "layer", "source", "moves"))
    assert (entry["layer"], entry["source"], entry["moves"]) == (
        "trainer", "program_counter", "tokens_per_s_per_chip")
    assert "workloads" not in entry     # every cell runs a trainer
    assert (spec["reader"], spec["prefix"]) == ("counter_per_round",
                                                "trainer.round_chunks")
    # every cell reports it beside the metric it moves
    for cell in (w["name"] for w in man["workloads"]):
        assert NAME in {m["name"] for m in
                        manifest.metrics_of(man, "per_layer", cell)}


def test_it_reads_the_chunks_a_round_all_workers_summed():
    spec = manifest.layer_metric_spec(NAME)
    read = manifest.resolve(spec["reader"])
    # a bare window of one-chunk rounds: two workers, one chunk each
    one = [{"counters": {"van.messages_sent": 8.0 * i,
                         "trainer.round_chunks": 2.0 * i}}
           for i in range(5)]
    assert read(_ctx(one), spec) == 2.0
    # the shaped cell's plan: sixteen chunks a worker
    cut = [{"counters": {"van.messages_sent": 128.0 * i,
                         "trainer.round_chunks": 32.0 * i}}
           for i in range(5)]
    assert read(_ctx(cut), spec) == 32.0
    # a program that books no such counter (a parent) reports nothing
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    assert read(_ctx(bare), spec) is None
