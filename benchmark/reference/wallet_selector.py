"""The plain reference of a wallet's token selection and transfer assembly:
hyperledger-labs/fabric-token-sdk `token/services/selector` (the default,
"simple" selector) and `token/services/ttx` `Transaction.Transfer`,
transcribed as a pure function (no clock, no locks, no import of the
package under test).

    select(unspent, amount) -> (inputs, outputs)

`unspent` is the wallet's unspent amounts of one token type in the order
the vault's iterator yields them, `amount` what the wallet pays. The rule
(recalled: no network in this sandbox and /root/reference is not mounted):

1. walk the unspent tokens in order and take each one until the amounts
   taken cover `amount` (`selector.go: for each token: sum += quantity;
   if sum >= amount: break`); a token is never skipped for its size and
   none is taken after the sum covers the amount;
2. if the walk ends short of `amount`: insufficient funds, nothing is
   taken;
3. the transfer has one output to the recipient, `amount`, and, where the
   inputs hold more, a second output to the sender with the rest
   (`ttx`: "if sum > amount: add a change output"): `[amount]` when the
   amount is met exactly, `[amount, change]` otherwise.

So the shapes a population of wallets sends are `(k, 1)` and `(k, 2)`,
`k` from one upward, and nothing else: never two recipients' outputs in
one transfer of one `Transfer` call, never a superfluous input.

Departures from the SDK, each because one request is built at a time: no
token locks and no retry while another transaction holds a token (`unspent`
is what this request may take); one token type; amounts are whole numbers
(the SDK's quantities at the public parameters' precision). A wallet that
consolidates (`sweep`) pays itself the sum of the tokens it names: the
same rule with `amount` = their sum.
"""

from __future__ import annotations


class InsufficientFunds(ValueError):
    pass


def select(unspent, amount: int) -> tuple:
    """-> (the inputs taken, in order; the outputs `[amount]` or
    `[amount, change]`)."""
    if amount <= 0:
        raise ValueError("a wallet pays a positive amount")
    taken, total = [], 0
    for quantity in unspent:
        if total >= amount:
            break
        if quantity <= 0:
            raise ValueError("an unspent token holds a positive amount")
        taken.append(quantity)
        total += quantity
    if total < amount:
        raise InsufficientFunds(f"need {amount}, the wallet holds {total}")
    return taken, [amount] if total == amount else [amount, total - amount]


def is_selected(in_values, out_values) -> bool:
    """Whether a transfer `in_values -> out_values` is one this rule
    assembles: the payment is the first output, the inputs are the ones a
    wallet holding them (in that order, and any number more behind them)
    would have taken for it, and the outputs are payment or payment +
    change."""
    if not in_values or not out_values:
        return False
    try:
        taken, outputs = select(list(in_values) + [max(in_values)], out_values[0])
    except ValueError:
        return False
    return taken == list(in_values) and outputs == list(out_values)
