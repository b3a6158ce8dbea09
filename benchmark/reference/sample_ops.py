"""The plain reference of the token sample's three operations: what issue,
transfer and redeem (hyperledger/fabric-samples `token-sdk`: the issuer
node's `issue`, the owner nodes' `transfer` and `redeem`, over
hyperledger-labs/fabric-token-sdk `ttx`) do to the supply a ledger holds,
transcribed as pure functions of a group's slot plan and its verdicts (no
clock, no proofs, no import of the package under test).

    supply(slots, forms, verdicts) -> {"issued", "redeemed", "unspent", "ownerless"}

The rules (recalled: no network in this sandbox and /root/reference is not
mounted):

1. an *issue* spends nothing and creates its outputs for their owner;
2. a *transfer* spends its inputs and creates outputs of the same sum,
   each with an owner;
3. a *redeem* is a transfer whose first output has no owner (`ttx`: the
   redeemed amount leaves circulation) and whose further outputs are change
   to the sender: `in = redeem + change`;
4. a token is spent once; an output that has no owner is never an input
   (nobody can sign for it);
5. only a Valid request changes the ledger, so at every moment
   `issued - redeemed = the sum of the owners' unspent tokens`.

A group (`harness/schedule.py`) is one set-up issue, whose outputs the
group's slots spend one after another, and the slots' requests; a double
spend re-sends the inputs of an earlier slot (`of`).
"""

from __future__ import annotations


class Violation(ValueError):
    """A verdict, or a form, that no ledger keeping the rules can hold."""


def check_form(form: dict, top: int) -> None:
    """One request form of a mix (`op`, `in_values`, `out_values` or
    `redeem_value` + `change_values`) keeps the rules, and every amount is
    a token's: 0 < value < `top`."""
    op = form["op"]
    ins = list(form.get("in_values", []))
    if op == "issue":
        outs = list(form["out_values"])
        if ins:
            raise Violation("an issue spends nothing")
    elif op == "transfer":
        outs = list(form["out_values"])
        if not ins or sum(ins) != sum(outs):
            raise Violation("a transfer conserves: in = out")
    elif op == "redeem":
        outs = [form["redeem_value"], *form["change_values"]]
        if not ins or sum(ins) != sum(outs):
            raise Violation("a cash-out conserves: in = redeem + change")
    else:
        raise Violation(f"the sample has no operation {op!r}")
    if not outs or not all(0 < v < top for v in ins + outs):
        raise Violation("every amount is a token's: 0 < value < top")


def supply(slots: list, forms: dict, verdicts: list) -> dict:
    """The ledger a group leaves behind. `slots` is the group's plan
    (`{"kind", "form", "of"}` a slot, `harness/schedule.py`), `forms` the
    mix's forms by name, `verdicts` "Valid" or "Invalid" a slot, in slot
    order. -> `issued` and `redeemed` (sums), `unspent` (the owners'
    amounts, sorted) and `ownerless` (the redeemed outputs, sorted).
    Raises `Violation` where a Valid verdict breaks a rule: a token spent
    twice, an input that is not there."""
    form = [forms[s.get("form", "")] for s in slots]
    # the set-up issue: every slot's inputs, slot after slot
    live, first, issued = {}, [], 0
    for f in form:
        first.append(len(live))
        for v in f.get("in_values", []):
            live[("setup", len(live))] = v
            issued += v
    redeemed, ownerless = 0, []
    for i, (slot, verdict) in enumerate(zip(slots, verdicts)):
        if verdict != "Valid":
            continue
        # (a double spend re-sends the action of the slot it names)
        src = slot["of"] if slot["kind"] == "double_spend" else i
        f = form[src]
        spent = [("setup", first[src] + k)
                 for k in range(len(f.get("in_values", [])))]
        if any(t not in live for t in spent):
            raise Violation(f"slot {i}: Valid, but spends a token that is "
                            "spent already or was never there")
        for t in spent:
            del live[t]
        if f["op"] == "issue":
            outs, issued = f["out_values"], issued + sum(f["out_values"])
        elif f["op"] == "redeem":
            outs = f["change_values"]
            redeemed += f["redeem_value"]
            ownerless.append(f["redeem_value"])
        else:
            outs = f["out_values"]
        for k, v in enumerate(outs):
            live[(i, k)] = v
    out = {"issued": issued, "redeemed": redeemed,
           "unspent": sorted(live.values()), "ownerless": sorted(ownerless)}
    if issued - redeemed != sum(out["unspent"]):
        raise Violation(f"supply does not balance: {out}")
    return out
