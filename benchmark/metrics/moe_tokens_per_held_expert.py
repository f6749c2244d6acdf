"""Mean picks a held expert gets in one execution of an expert layer of
the decode program (the program's routing counters, whole process): the
``M`` of its grouped matmul. 256 slots x top-12 / 768 outputs = 4 at
uniform routing. The earlier line has every counter."""

from benchmark.lib import harness, longcat_readers as lr


def read(run, trace):
    got = lr.routing("decode")
    if not got or not got["held"]:
        return None
    harness.log({"moe_routing": {"decode": got,
                                 "prefill": lr.routing("prefill")}})
    return sum(got["held"]) / len(got["held"]) / got["layer_calls"]
