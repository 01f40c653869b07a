"""A number the runner took itself (host clock or a program counter)."""


def reduce(facts, args):
    return facts["values"].get(args["key"])
