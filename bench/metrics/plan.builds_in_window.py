"""Plans the PlanStore lowered or specialised inside the window
(``misses`` + ``shares``): a shape that set-up did not warm."""


def read(res):
    if res["kind"] != "serve":
        return None
    a, b = res["stats0"]["plan_store"], res["stats1"]["plan_store"]
    return (b["misses"] + b["shares"]) - (a["misses"] + a["shares"])
