"""The largest held expert's load over the mean held load, in the worst
expert layer of each step, averaged over the window's steps (program
counter: the train step's ``moe_load_imbalance``)."""


def read(record, trace):
    return record.get("moe_load_imbalance")
