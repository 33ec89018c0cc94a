// Generated by repro.codegen (paper Table I) — spec lstm_2i_1x4_2o

// cell=lstm steps=6 unroll=1 c_slow=1 width=16

module Create_mult #(parameter WIDTH = 16) (
  input  wire                      clk,
  input  wire                      en,
  input  wire                      clr,
  input  wire signed [WIDTH-1:0]   a,     // datapath operand
  input  wire signed [WIDTH-1:0]   w,     // coefficient (ROM port)
  output reg  signed [2*WIDTH-1:0] acc    // wide accumulator
);
  always @(posedge clk) begin
    if (clr)     acc <= {2*WIDTH{1'b0}};
    else if (en) acc <= acc + a * w;
  end
endmodule

module Create_AF_sigmoid #(parameter WIDTH = 16) (
  input  wire                    clk,
  input  wire signed [WIDTH-1:0] x,     // Q(4.12) MACC result
  output reg  signed [WIDTH-1:0] y
);
  // ROM LUT: sigmoid sampled on [-4, 4), 64 entries.
  // addr = clamp(x, -4, 4) mapped linearly: bias by +4
  // (= 1 << WIDTH-2 in Q4.12), saturate to [0, 8), take the top
  // 6 magnitude bits.
  wire signed [WIDTH:0] biased = {x[WIDTH-1], x} + (1 <<< (WIDTH - 2));
  wire [5:0] addr =
      (biased < 0)                    ? 6'd0 :
      (biased >= (1 <<< (WIDTH - 1))) ? 6'd63 :
      biased[WIDTH-2 -: 6];
  always @(posedge clk) begin
    case (addr)
      6'd0: y <= 16'h004e;
      6'd1: y <= 16'h0059;
      6'd2: y <= 16'h0064;
      6'd3: y <= 16'h0071;
      6'd4: y <= 16'h0080;
      6'd5: y <= 16'h0090;
      6'd6: y <= 16'h00a2;
      6'd7: y <= 16'h00b7;
      6'd8: y <= 16'h00ce;
      6'd9: y <= 16'h00e8;
      6'd10: y <= 16'h0105;
      6'd11: y <= 16'h0125;
      6'd12: y <= 16'h0149;
      6'd13: y <= 16'h0171;
      6'd14: y <= 16'h019d;
      6'd15: y <= 16'h01ce;
      6'd16: y <= 16'h0204;
      6'd17: y <= 16'h023f;
      6'd18: y <= 16'h027f;
      6'd19: y <= 16'h02c6;
      6'd20: y <= 16'h0312;
      6'd21: y <= 16'h0365;
      6'd22: y <= 16'h03bd;
      6'd23: y <= 16'h041c;
      6'd24: y <= 16'h0481;
      6'd25: y <= 16'h04eb;
      6'd26: y <= 16'h055a;
      6'd27: y <= 16'h05cf;
      6'd28: y <= 16'h0647;
      6'd29: y <= 16'h06c3;
      6'd30: y <= 16'h0741;
      6'd31: y <= 16'h07c0;
      6'd32: y <= 16'h0840;
      6'd33: y <= 16'h08bf;
      6'd34: y <= 16'h093d;
      6'd35: y <= 16'h09b9;
      6'd36: y <= 16'h0a31;
      6'd37: y <= 16'h0aa6;
      6'd38: y <= 16'h0b15;
      6'd39: y <= 16'h0b7f;
      6'd40: y <= 16'h0be4;
      6'd41: y <= 16'h0c43;
      6'd42: y <= 16'h0c9b;
      6'd43: y <= 16'h0cee;
      6'd44: y <= 16'h0d3a;
      6'd45: y <= 16'h0d81;
      6'd46: y <= 16'h0dc1;
      6'd47: y <= 16'h0dfc;
      6'd48: y <= 16'h0e32;
      6'd49: y <= 16'h0e63;
      6'd50: y <= 16'h0e8f;
      6'd51: y <= 16'h0eb7;
      6'd52: y <= 16'h0edb;
      6'd53: y <= 16'h0efb;
      6'd54: y <= 16'h0f18;
      6'd55: y <= 16'h0f32;
      6'd56: y <= 16'h0f49;
      6'd57: y <= 16'h0f5e;
      6'd58: y <= 16'h0f70;
      6'd59: y <= 16'h0f80;
      6'd60: y <= 16'h0f8f;
      6'd61: y <= 16'h0f9c;
      6'd62: y <= 16'h0fa7;
      6'd63: y <= 16'h0fb2;
      default: y <= {WIDTH{1'b0}};
    endcase
  end
endmodule

module Create_AF_tanh #(parameter WIDTH = 16) (
  input  wire                    clk,
  input  wire signed [WIDTH-1:0] x,     // Q(4.12) MACC result
  output reg  signed [WIDTH-1:0] y
);
  // ROM LUT: tanh sampled on [-4, 4), 64 entries.
  // addr = clamp(x, -4, 4) mapped linearly: bias by +4
  // (= 1 << WIDTH-2 in Q4.12), saturate to [0, 8), take the top
  // 6 magnitude bits.
  wire signed [WIDTH:0] biased = {x[WIDTH-1], x} + (1 <<< (WIDTH - 2));
  wire [5:0] addr =
      (biased < 0)                    ? 6'd0 :
      (biased >= (1 <<< (WIDTH - 1))) ? 6'd63 :
      biased[WIDTH-2 -: 6];
  always @(posedge clk) begin
    case (addr)
      6'd0: y <= 16'hf003;
      6'd1: y <= 16'hf004;
      6'd2: y <= 16'hf005;
      6'd3: y <= 16'hf007;
      6'd4: y <= 16'hf008;
      6'd5: y <= 16'hf00b;
      6'd6: y <= 16'hf00e;
      6'd7: y <= 16'hf012;
      6'd8: y <= 16'hf017;
      6'd9: y <= 16'hf01d;
      6'd10: y <= 16'hf026;
      6'd11: y <= 16'hf030;
      6'd12: y <= 16'hf03e;
      6'd13: y <= 16'hf050;
      6'd14: y <= 16'hf066;
      6'd15: y <= 16'hf082;
      6'd16: y <= 16'hf0a7;
      6'd17: y <= 16'hf0d5;
      6'd18: y <= 16'hf10f;
      6'd19: y <= 16'hf159;
      6'd20: y <= 16'hf1b5;
      6'd21: y <= 16'hf229;
      6'd22: y <= 16'hf2b9;
      6'd23: y <= 16'hf36a;
      6'd24: y <= 16'hf441;
      6'd25: y <= 16'hf544;
      6'd26: y <= 16'hf675;
      6'd27: y <= 16'hf7d8;
      6'd28: y <= 16'hf96a;
      6'd29: y <= 16'hfb28;
      6'd30: y <= 16'hfd09;
      6'd31: y <= 16'hff00;
      6'd32: y <= 16'h0100;
      6'd33: y <= 16'h02f7;
      6'd34: y <= 16'h04d8;
      6'd35: y <= 16'h0696;
      6'd36: y <= 16'h0828;
      6'd37: y <= 16'h098b;
      6'd38: y <= 16'h0abc;
      6'd39: y <= 16'h0bbf;
      6'd40: y <= 16'h0c96;
      6'd41: y <= 16'h0d47;
      6'd42: y <= 16'h0dd7;
      6'd43: y <= 16'h0e4b;
      6'd44: y <= 16'h0ea7;
      6'd45: y <= 16'h0ef1;
      6'd46: y <= 16'h0f2b;
      6'd47: y <= 16'h0f59;
      6'd48: y <= 16'h0f7e;
      6'd49: y <= 16'h0f9a;
      6'd50: y <= 16'h0fb0;
      6'd51: y <= 16'h0fc2;
      6'd52: y <= 16'h0fd0;
      6'd53: y <= 16'h0fda;
      6'd54: y <= 16'h0fe3;
      6'd55: y <= 16'h0fe9;
      6'd56: y <= 16'h0fee;
      6'd57: y <= 16'h0ff2;
      6'd58: y <= 16'h0ff5;
      6'd59: y <= 16'h0ff8;
      6'd60: y <= 16'h0ff9;
      6'd61: y <= 16'h0ffb;
      6'd62: y <= 16'h0ffc;
      6'd63: y <= 16'h0ffd;
      default: y <= {WIDTH{1'b0}};
    endcase
  end
endmodule

module Create_Layer_layer0_z #(parameter WIDTH = 16, parameter J = 1) (
  input  wire                        clk,
  input  wire                        start,
  input  wire [$clog2(6)-1:0]        k,      // FSM step (ROM page select)
  input  wire signed [6*WIDTH-1:0]  x_bus,  // input bus (6 lanes)
  output wire signed [16*WIDTH-1:0] z_bus,  // MACC results (16 lanes)
  output reg                         done
);
  // coefficient ROM: 1 page(s) x 16x6 words
  reg signed [WIDTH-1:0] rom [0:95];
  reg signed [WIDTH-1:0] rom_b [0:15];  // bias ROM, one word per lane
  initial begin
    rom[0] = 16'h039c;
    rom[1] = 16'h08ba;
    rom[2] = 16'h0475;
    rom[3] = 16'hf9c7;
    rom[4] = 16'hf84c;
    rom[5] = 16'h108f;
    rom[6] = 16'hfc96;
    rom[7] = 16'h0270;
    rom[8] = 16'h0081;
    rom[9] = 16'hfc55;
    rom[10] = 16'h011f;
    rom[11] = 16'hf683;
    rom[12] = 16'hf5ac;
    rom[13] = 16'hfebb;
    rom[14] = 16'h127e;
    rom[15] = 16'hfa9a;
    rom[16] = 16'hfb4b;
    rom[17] = 16'hf173;
    rom[18] = 16'hf48e;
    rom[19] = 16'hf454;
    rom[20] = 16'hfb70;
    rom[21] = 16'h030a;
    rom[22] = 16'h12ae;
    rom[23] = 16'hf628;
    rom[24] = 16'hf760;
    rom[25] = 16'h00b1;
    rom[26] = 16'hfddf;
    rom[27] = 16'h076a;
    rom[28] = 16'hff7a;
    rom[29] = 16'hff39;
    rom[30] = 16'hfc3a;
    rom[31] = 16'h0274;
    rom[32] = 16'hf933;
    rom[33] = 16'h0fd9;
    rom[34] = 16'h0064;
    rom[35] = 16'hfb56;
    rom[36] = 16'h0463;
    rom[37] = 16'hff5a;
    rom[38] = 16'hff42;
    rom[39] = 16'hfa80;
    rom[40] = 16'h049f;
    rom[41] = 16'hefaf;
    rom[42] = 16'hff6d;
    rom[43] = 16'h031a;
    rom[44] = 16'h0bee;
    rom[45] = 16'hff7e;
    rom[46] = 16'hfe50;
    rom[47] = 16'h008c;
    rom[48] = 16'hff0f;
    rom[49] = 16'h00ba;
    rom[50] = 16'h07f5;
    rom[51] = 16'h0464;
    rom[52] = 16'hfb0d;
    rom[53] = 16'hfad9;
    rom[54] = 16'h0270;
    rom[55] = 16'hfced;
    rom[56] = 16'h0bf3;
    rom[57] = 16'h0b2b;
    rom[58] = 16'h0208;
    rom[59] = 16'h031f;
    rom[60] = 16'h026e;
    rom[61] = 16'h0731;
    rom[62] = 16'hf856;
    rom[63] = 16'h0169;
    rom[64] = 16'hfc20;
    rom[65] = 16'h0817;
    rom[66] = 16'h03cc;
    rom[67] = 16'h0153;
    rom[68] = 16'hfcbb;
    rom[69] = 16'h021f;
    rom[70] = 16'h0706;
    rom[71] = 16'hfbac;
    rom[72] = 16'hf21e;
    rom[73] = 16'hf6dc;
    rom[74] = 16'hfcb9;
    rom[75] = 16'h1141;
    rom[76] = 16'hfaf1;
    rom[77] = 16'h04f4;
    rom[78] = 16'hf14a;
    rom[79] = 16'h0ba0;
    rom[80] = 16'h02ac;
    rom[81] = 16'hf939;
    rom[82] = 16'h0a8d;
    rom[83] = 16'h031a;
    rom[84] = 16'hfc28;
    rom[85] = 16'hfe21;
    rom[86] = 16'h091f;
    rom[87] = 16'h0acd;
    rom[88] = 16'h03e8;
    rom[89] = 16'h0eac;
    rom[90] = 16'hfd92;
    rom[91] = 16'h02d9;
    rom[92] = 16'h09fb;
    rom[93] = 16'hfcc1;
    rom[94] = 16'hf622;
    rom[95] = 16'hf63e;
  end
  initial begin
    rom_b[0] = 16'h0000;
    rom_b[1] = 16'h0000;
    rom_b[2] = 16'h0000;
    rom_b[3] = 16'h0000;
    rom_b[4] = 16'h1000;
    rom_b[5] = 16'h1000;
    rom_b[6] = 16'h1000;
    rom_b[7] = 16'h1000;
    rom_b[8] = 16'h0000;
    rom_b[9] = 16'h0000;
    rom_b[10] = 16'h0000;
    rom_b[11] = 16'h0000;
    rom_b[12] = 16'h0000;
    rom_b[13] = 16'h0000;
    rom_b[14] = 16'h0000;
    rom_b[15] = 16'h0000;
  end
  reg [$clog2(6):0] cyc;  // 6 serial MACC cycles (J = 1 copies)
  genvar gi, ji;
  generate
    for (gi = 0; gi < 16; gi = gi + 1) begin : lane
      // J parallel Create_mult copies stride the input bus; term ji covers
      // element cyc*J + ji (zero-padded past in_width), summed combinationally
      wire signed [2*WIDTH-1:0] acc [0:J-1];
      wire signed [2*WIDTH-1:0] acc_sum [0:J];
      assign acc_sum[0] = {2*WIDTH{1'b0}};
      for (ji = 0; ji < J; ji = ji + 1) begin : copy
        wire [31:0] idx = cyc * J + ji;
        wire        pad = (idx >= 6);
        Create_mult #(.WIDTH(WIDTH)) u_mult (
          .clk(clk), .en(~done & ~pad), .clr(start),
          .a(x_bus[(idx % 6)*WIDTH +: WIDTH]),
          .w(rom[gi*6 + (idx % 6)]),
          .acc(acc[ji])
        );
        assign acc_sum[ji+1] = acc_sum[ji] + acc[ji];
      end
      assign z_bus[gi*WIDTH +: WIDTH] = acc_sum[J][2*WIDTH-1-4 -: WIDTH] + rom_b[gi];  // Q-align
    end
  endgenerate
  always @(posedge clk) begin
    if (start) begin cyc <= 0; done <= 1'b0; end
    else if (!done) begin
      cyc  <= cyc + 1;
      done <= (cyc == 5);
    end
  end
endmodule

module Create_Layer_End_C #(parameter WIDTH = 16, parameter J = 1) (
  input  wire                        clk,
  input  wire                        start,
  input  wire [$clog2(2)-1:0]        k,      // FSM step (ROM page select)
  input  wire signed [4*WIDTH-1:0]  x_bus,  // input bus (4 lanes)
  output wire signed [2*WIDTH-1:0] z_bus,  // MACC results (2 lanes)
  output reg                         done
);
  // coefficient ROM: 1 page(s) x 2x4 words
  reg signed [WIDTH-1:0] rom [0:7];
  initial begin
    rom[0] = 16'h0a5d;
    rom[1] = 16'h0ad7;
    rom[2] = 16'hfcb9;
    rom[3] = 16'hf9d3;
    rom[4] = 16'h030c;
    rom[5] = 16'h0027;
    rom[6] = 16'h0499;
    rom[7] = 16'hf9f9;
  end
  reg [$clog2(4):0] cyc;  // 4 serial MACC cycles (J = 1 copies)
  genvar gi, ji;
  generate
    for (gi = 0; gi < 2; gi = gi + 1) begin : lane
      // J parallel Create_mult copies stride the input bus; term ji covers
      // element cyc*J + ji (zero-padded past in_width), summed combinationally
      wire signed [2*WIDTH-1:0] acc [0:J-1];
      wire signed [2*WIDTH-1:0] acc_sum [0:J];
      assign acc_sum[0] = {2*WIDTH{1'b0}};
      for (ji = 0; ji < J; ji = ji + 1) begin : copy
        wire [31:0] idx = cyc * J + ji;
        wire        pad = (idx >= 4);
        Create_mult #(.WIDTH(WIDTH)) u_mult (
          .clk(clk), .en(~done & ~pad), .clr(start),
          .a(x_bus[(idx % 4)*WIDTH +: WIDTH]),
          .w(rom[gi*4 + (idx % 4)]),
          .acc(acc[ji])
        );
        assign acc_sum[ji+1] = acc_sum[ji] + acc[ji];
      end
      assign z_bus[gi*WIDTH +: WIDTH] = acc_sum[J][2*WIDTH-1-4 -: WIDTH];  // Q-align
    end
  endgenerate
  always @(posedge clk) begin
    if (start) begin cyc <= 0; done <= 1'b0; end
    else if (!done) begin
      cyc  <= cyc + 1;
      done <= (cyc == 3);
    end
  end
endmodule

module Create_Datapath_layer0 #(parameter WIDTH = 16) (
  input  wire clk,
  input  wire start,
  input  wire load,
  input  wire [$clog2(6)-1:0] k,
  input  wire signed [2*WIDTH-1:0] u_bus,
  input  wire signed [4*WIDTH-1:0] c_init,
  output wire signed [4*WIDTH-1:0] c_bus,
  input  wire signed [4*WIDTH-1:0] h_init,
  output wire signed [4*WIDTH-1:0] h_bus,
  output wire signed [4*WIDTH-1:0] y_bus,
  output wire step_done
);
  wire signed [2*WIDTH-1:0] w_u;  assign w_u = u_bus;
  reg signed [4*WIDTH-1:0] r_h;  // state register
  wire signed [4*WIDTH-1:0] w_h;  assign w_h = r_h;
  reg signed [4*WIDTH-1:0] r_c;  // state register
  wire signed [4*WIDTH-1:0] w_c;  assign w_c = r_c;
  wire signed [6*WIDTH-1:0] w_xu;  assign w_xu = {w_h, w_u};
  // const ROM 'W' [6x16]
  // const ROM 'b' [1x16]
  wire signed [16*WIDTH-1:0] w_z;
  wire d_z;
  Create_Layer_layer0_z #(.WIDTH(WIDTH)) u_z (
    .clk(clk), .start(start), .k(k),
    .x_bus(w_xu), .z_bus(w_z), .done(d_z)
  );  // 6 -> 16 MACC array + bias ROM
  wire signed [4*WIDTH-1:0] w_z_i;  assign w_z_i = w_z[0*WIDTH +: 4*WIDTH];
  wire signed [4*WIDTH-1:0] w_i_gate;
  genvar ai_i_gate;
  generate
    for (ai_i_gate = 0; ai_i_gate < 4; ai_i_gate = ai_i_gate + 1) begin : af_i_gate
      Create_AF_sigmoid #(.WIDTH(WIDTH)) u_i_gate (.clk(clk),
        .x(w_z_i[ai_i_gate*WIDTH +: WIDTH]), .y(w_i_gate[ai_i_gate*WIDTH +: WIDTH]));
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_z_f;  assign w_z_f = w_z[4*WIDTH +: 4*WIDTH];
  wire signed [4*WIDTH-1:0] w_f_gate;
  genvar ai_f_gate;
  generate
    for (ai_f_gate = 0; ai_f_gate < 4; ai_f_gate = ai_f_gate + 1) begin : af_f_gate
      Create_AF_sigmoid #(.WIDTH(WIDTH)) u_f_gate (.clk(clk),
        .x(w_z_f[ai_f_gate*WIDTH +: WIDTH]), .y(w_f_gate[ai_f_gate*WIDTH +: WIDTH]));
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_z_g;  assign w_z_g = w_z[8*WIDTH +: 4*WIDTH];
  wire signed [4*WIDTH-1:0] w_g_gate;
  genvar ai_g_gate;
  generate
    for (ai_g_gate = 0; ai_g_gate < 4; ai_g_gate = ai_g_gate + 1) begin : af_g_gate
      Create_AF_tanh #(.WIDTH(WIDTH)) u_g_gate (.clk(clk),
        .x(w_z_g[ai_g_gate*WIDTH +: WIDTH]), .y(w_g_gate[ai_g_gate*WIDTH +: WIDTH]));
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_z_o;  assign w_z_o = w_z[12*WIDTH +: 4*WIDTH];
  wire signed [4*WIDTH-1:0] w_o_gate;
  genvar ai_o_gate;
  generate
    for (ai_o_gate = 0; ai_o_gate < 4; ai_o_gate = ai_o_gate + 1) begin : af_o_gate
      Create_AF_sigmoid #(.WIDTH(WIDTH)) u_o_gate (.clk(clk),
        .x(w_z_o[ai_o_gate*WIDTH +: WIDTH]), .y(w_o_gate[ai_o_gate*WIDTH +: WIDTH]));
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_fc;  // elementwise mul, 4 VPU lanes
  genvar ei_fc;
  generate
    for (ei_fc = 0; ei_fc < 4; ei_fc = ei_fc + 1) begin : ew_fc
      wire signed [2*WIDTH-1:0] p = $signed(w_f_gate[ei_fc*WIDTH +: WIDTH]) * $signed(w_c[ei_fc*WIDTH +: WIDTH]);
      assign w_fc[ei_fc*WIDTH +: WIDTH] = p[2*WIDTH-1-4 -: WIDTH];
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_ig;  // elementwise mul, 4 VPU lanes
  genvar ei_ig;
  generate
    for (ei_ig = 0; ei_ig < 4; ei_ig = ei_ig + 1) begin : ew_ig
      wire signed [2*WIDTH-1:0] p = $signed(w_i_gate[ei_ig*WIDTH +: WIDTH]) * $signed(w_g_gate[ei_ig*WIDTH +: WIDTH]);
      assign w_ig[ei_ig*WIDTH +: WIDTH] = p[2*WIDTH-1-4 -: WIDTH];
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_c_next;  // elementwise add, 4 VPU lanes
  genvar ei_c_next;
  generate
    for (ei_c_next = 0; ei_c_next < 4; ei_c_next = ei_c_next + 1) begin : ew_c_next
      assign w_c_next[ei_c_next*WIDTH +: WIDTH] = $signed(w_fc[ei_c_next*WIDTH +: WIDTH]) + $signed(w_ig[ei_c_next*WIDTH +: WIDTH]);
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_c_tanh;
  genvar ai_c_tanh;
  generate
    for (ai_c_tanh = 0; ai_c_tanh < 4; ai_c_tanh = ai_c_tanh + 1) begin : af_c_tanh
      Create_AF_tanh #(.WIDTH(WIDTH)) u_c_tanh (.clk(clk),
        .x(w_c_next[ai_c_tanh*WIDTH +: WIDTH]), .y(w_c_tanh[ai_c_tanh*WIDTH +: WIDTH]));
    end
  endgenerate
  wire signed [4*WIDTH-1:0] w_h_next;  // elementwise mul, 4 VPU lanes
  genvar ei_h_next;
  generate
    for (ei_h_next = 0; ei_h_next < 4; ei_h_next = ei_h_next + 1) begin : ew_h_next
      wire signed [2*WIDTH-1:0] p = $signed(w_o_gate[ei_h_next*WIDTH +: WIDTH]) * $signed(w_c_tanh[ei_h_next*WIDTH +: WIDTH]);
      assign w_h_next[ei_h_next*WIDTH +: WIDTH] = p[2*WIDTH-1-4 -: WIDTH];
    end
  endgenerate
  assign step_done = d_z;
  // ONE register write-back per start kick (step_done is a sticky level
  // that only clears on the next start pulse).  AF_DEPTH settle cycles let
  // the registered AF ROM chain propagate the FINAL MACC sum (one clock per
  // chained ROM) before the state registers latch.
  localparam AF_DEPTH = 2;
  reg stepped;
  reg [2:0] af_wait;
  always @(posedge clk) begin
    if (load) begin
      stepped <= 1'b0; af_wait <= 3'd0;
      r_c <= c_init;
      r_h <= h_init;
    end else if (start) begin
      stepped <= 1'b0; af_wait <= 3'd0;
    end else if (step_done && af_wait < AF_DEPTH) begin
      af_wait <= af_wait + 3'd1;
    end else if (step_done && !stepped) begin
      stepped <= 1'b1;
      r_c <= w_c_next;
      r_h <= w_h_next;
    end
  end
  assign c_bus = r_c;
  assign h_bus = r_h;
  assign y_bus = w_h_next;
endmodule

module Create_TopModule_lstm_2i_1x4_2o #(parameter WIDTH = 16) (
  input  wire                       clk,
  input  wire                       rst,
  input  wire                       start,
  input  wire signed [2*WIDTH-1:0]   u_bus,
  output wire                       u_ready,  // present u[u_k] on u_bus
  output wire [$clog2(6)-1:0]       u_k,
  output wire signed [2*WIDTH-1:0]  y_bus,
  output reg                        done
);
  // FSM: IDLE -> LOAD -> ITERATE x 6 -> READOUT -> DONE
  localparam S_IDLE = 3'd0, S_LOAD = 3'd1, S_ITER = 3'd2,
             S_READ = 3'd3, S_DONE = 3'd4;
  localparam STEPS = 6, CSLOW = 1, J = 1;
  localparam SETTLE = 4;  // last stage AF chain + write-back

  reg [2:0] fsm_state;
  reg [$clog2(6)-1:0] fsm_k;  // the time-multiplex counter
  // MACC layers treat start as a synchronous clear, so every use is kicked
  // by a ONE-CYCLE pulse; transitions qualify on !kick to let the sticky
  // done levels clear after each kick.
  reg kick;        // per-step start pulse into the first stage datapath
  reg load_kick;   // input-injection start (Create_Layer1)
  reg read_kick;   // readout start (Create_Layer_End)
  reg [2:0] settle;  // AF-ROM chain + write-back cycles before advancing
  wire step_start = kick;
  wire load       = (fsm_state == S_LOAD);
  assign u_ready = kick;
  assign u_k     = fsm_k;
  wire signed [4*WIDTH-1:0] layer0_c;
  wire signed [4*WIDTH-1:0] layer0_c_init = {4*WIDTH{1'b0}};
  wire signed [4*WIDTH-1:0] layer0_h;
  wire signed [4*WIDTH-1:0] layer0_h_init = {4*WIDTH{1'b0}};
  wire signed [4*WIDTH-1:0] y_layer0;
  wire done_layer0;
  Create_Datapath_layer0 #(.WIDTH(WIDTH)) u_layer0 (
    .clk(clk), .start(step_start), .load(load), .k(fsm_k),
    .u_bus(u_bus),
    .c_init(layer0_c_init),
    .c_bus(layer0_c),
    .h_init(layer0_h_init),
    .h_bus(layer0_h),
    .y_bus(y_layer0),
    .step_done(done_layer0)
  );
  reg done_layer0_q;
  always @(posedge clk) done_layer0_q <= done_layer0;
  wire step_done_all = done_layer0 & ~done_layer0_q;
  // recurrent cells: state registers load zero; u_bus streams per step
  wire load_done = 1'b1;
  // Create_Layer_End: readout y = C x[N] on the final carry
  wire signed [4*WIDTH-1:0] x_final = layer0_h;
  wire read_done;
  Create_Layer_End_C #(.WIDTH(WIDTH)) u_readout (
    .clk(clk), .start(read_kick), .k(1'b0),
    .x_bus(x_final), .z_bus(y_bus), .done(read_done)
  );
  always @(posedge clk) begin
    if (rst) begin
      fsm_state <= S_IDLE; fsm_k <= 0; done <= 1'b0;
      kick <= 1'b0; load_kick <= 1'b0; read_kick <= 1'b0; settle <= 3'd0;
    end else begin
      kick <= 1'b0; load_kick <= 1'b0; read_kick <= 1'b0;
      case (fsm_state)
        S_IDLE: if (start) begin fsm_state <= S_LOAD; load_kick <= 1'b1; end
        S_LOAD: if (load_done && !load_kick) begin
          fsm_state <= S_ITER; fsm_k <= 0; kick <= 1'b1;
        end
        S_ITER: begin
          // done EDGE -> SETTLE cycles (AF ROM chain, then register
          // write-back) -> next kick / readout
          if (settle == SETTLE) begin
            settle <= 3'd0;
            if (fsm_k == STEPS - 1) begin fsm_state <= S_READ; read_kick <= 1'b1; end
            else begin fsm_k <= fsm_k + 1; kick <= 1'b1; end  // next use
          end else if (settle != 3'd0) begin
            settle <= settle + 3'd1;
          end else if (step_done_all) begin
            settle <= 3'd1;
          end
        end
        S_READ: if (read_done && !read_kick) fsm_state <= S_DONE;
        S_DONE: begin done <= 1'b1; fsm_state <= S_IDLE; end
      endcase
    end
  end
endmodule
