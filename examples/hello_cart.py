#!/usr/bin/env python
"""HelloCart — port of the reference sample (samples/HelloCart, v1 in-memory
pair): products and carts with transparent caching and command-driven
cascading invalidation, plus a `changes()` watcher that live-prints totals.

Run: python examples/hello_cart.py

Scalar nodes only, four products, no device backend. The same sample as a
TABLE-BACKED service at TPC-C's scale (100,000 products, 3,000,000 carts,
3,000,000 derived totals kept hot on the device: ``TableBacking(hot=True)``),
served to subscribed clients, is the benchmark's deployment
``benchmarks/deployments/cart_served.py`` (configuration
``benchmarks/configs/hellocart-w100-1c.json``).
"""
import asyncio
import os
import sys
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stl_fusion_tpu.commands import command_handler
from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    capture,
    compute_method,
    is_invalidating,
)
from stl_fusion_tpu.utils.serialization import wire_type
import dataclasses


@wire_type
@dataclasses.dataclass(frozen=True)
class Product:
    id: str
    price: float


@wire_type
@dataclasses.dataclass(frozen=True)
class Cart:
    id: str
    item_ids: tuple


@wire_type
@dataclasses.dataclass(frozen=True)
class EditCommand:
    product: Product


class ProductService(ComputeService):
    """≈ InMemoryProductService (samples/HelloCart/v1)."""

    def __init__(self, hub=None):
        super().__init__(hub)
        self._products: Dict[str, Product] = {}

    @compute_method
    async def get(self, product_id: str) -> Optional[Product]:
        return self._products.get(product_id)

    @command_handler
    async def edit(self, command: EditCommand):
        if is_invalidating():
            # the invalidation idiom: reading in the invalidate scope marks
            # exactly this key stale (InMemoryCartService.cs:16-19)
            await self.get(command.product.id)
            return
        self._products[command.product.id] = command.product


class CartService(ComputeService):
    def __init__(self, products: ProductService, hub=None):
        super().__init__(hub)
        self.products = products
        self._carts: Dict[str, Cart] = {}

    def add(self, cart: Cart):
        self._carts[cart.id] = cart

    @compute_method
    async def get_total(self, cart_id: str) -> float:
        cart = self._carts.get(cart_id)
        if cart is None:
            return 0.0
        total = 0.0
        for pid in cart.item_ids:
            product = await self.products.get(pid)  # dependency captured here
            if product is not None:
                total += product.price
        return total


async def main():
    hub = FusionHub()
    hub.commander.attach_operations_pipeline()
    products = ProductService(hub)
    carts = CartService(products, hub)
    hub.commander.add_service(products)

    await hub.commander.call(EditCommand(Product("apple", 2.0)))
    await hub.commander.call(EditCommand(Product("banana", 0.5)))
    carts.add(Cart("cart:alice", ("apple", "apple", "banana")))

    total_computed = await capture(lambda: carts.get_total("cart:alice"))
    print(f"initial total: {total_computed.value}")

    async def watch():
        async for c in total_computed.changes():
            print(f"  watcher sees total = {c.output.value}")
            if c.output.value == 0.0:
                return

    watcher = asyncio.ensure_future(watch())
    await asyncio.sleep(0.05)

    for price in (3.0, 4.5, 0.0):
        await hub.commander.call(EditCommand(Product("apple", price)))
        await asyncio.sleep(0.05)
        if price == 0.0:
            await hub.commander.call(EditCommand(Product("banana", 0.0)))
            await asyncio.sleep(0.05)

    await asyncio.wait_for(watcher, 5.0)
    print("done: every edit cascaded into the cart total, zero polling")


if __name__ == "__main__":
    asyncio.run(main())
