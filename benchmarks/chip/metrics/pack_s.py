"""pack_s: seconds the program spent filling the padded client corpus and
handing it to ``device_put``, from its ``{"event": "pack"}`` record of the
first chunk (the program's host clock, ``FederatedTrainer.device_dataset``);
None where the first chunk packed nothing."""


def read(ctx):
    for event in ctx.events:
        if event["event"] == "pack":
            return event["seconds"]
    return None
